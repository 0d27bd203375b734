"""The closed forms and the table of peaks."""

import pytest

from benchmark.harness import flops, hlo, peaks


def test_bert_base_operations_per_token():
    # ISSUE 22: 587 MFLOP per token at 512 tokens, 15% predicted
    got = flops.encoder_train_flops_per_token(768, 12, 512, 30522, 0.15)
    assert got == pytest.approx(587.5e6, rel=2e-3)


def test_gpt2_small_operations_per_token_count_half_the_keys():
    got = flops.decoder_train_flops_per_token(768, 12, 4096, 50257)
    want = 3 * (12 * (24 * 768 ** 2 + 4 * 2048 * 768) + 2 * 768 * 50257)
    assert got == want


def test_flash_tiled_is_compute_bound_at_the_cell_shape():
    f, b = flops.flash_tiled_step_cost(4, 12, 4096, 64, 12)
    assert f == 12 * 48 * 18 * 4096 * 4096 * 64 / 2
    least, bound = flops.roofline_seconds(f, b, peaks.peaks("TPU v5 lite"))
    assert bound == "compute" and least == pytest.approx(f / 197e12)
    _, bound = flops.roofline_seconds(1e6, 1e9, peaks.peaks("TPU v5 lite"))
    assert bound == "memory"


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("cpu")


HLO = '''
ENTRY %main {
  %fusion.1 = bf16[8]{0} fusion(%p0), kind=kLoop
  %attn_fwd.3 = bf16[8]{0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", backend_config={}
  ROOT %custom-call.7 = f32[8]{0} custom-call(%attn_fwd.3), custom_call_target="tpu_custom_call"
  %ar = f32[8]{0} all-reduce(%x), replica_groups={}
  %art = (f32[8]{0}, f32[8]{0}, /*index=2*/f32[8]{0}) all-reduce(%x, %ar, %ar), to_apply=%add
  %ars = f32[8]{0} all-reduce-start(%x)
  %ard = f32[8]{0} all-reduce-done(%ars)
  %cp = f32[8]{0} custom-call(%x), custom_call_target="Sharding"
}
'''


def test_hlo_facts():
    assert hlo.custom_calls(HLO) == 2
    assert hlo.custom_call_names(HLO) == ["attn_fwd.3", "custom-call.7"]
    assert hlo.collectives(HLO) == {"all-reduce": 3}
