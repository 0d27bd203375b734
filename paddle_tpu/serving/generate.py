"""KV-cache generation: prefill + single-token decode programs.

``GPTGenerator`` owns the two programs a decoder (``models/gpt.py``'s
``GPTDecoder``, or a family on ``models/decoder.py``'s ``Decoder``:
afmoe, nemotron_h, dots_vlm, qwen3_next, minicpm_sala) splits itself
into and the Scope their state persistables share:

* prefill — embed the [B, S] context ONCE, fill every layer's
  ``gpt_l{i}_cache_{k,v}`` persistable slots 0..S-1, emit the last
  position's logits;
* decode — embed ONE token at a runtime position, append its K/V rows to
  the caches (in-place: the Executor donates mutated persistables, so the
  update is an HBM dynamic-update-slice), attend over the cache, emit
  next-token logits.

The caches are ``[batch, slots, kv_heads * head_dim]`` arrays, a position
a row, the layout a decode step writes with no transposition and
``kv_cache_attention`` reads without a copy (one Pallas kernel, kernels/
decode_attention.py); ``ops/kv_cache.py::cache_shape`` owns that shape
(``slots`` is ``max_len``, or a ring of the window's length on a
sliding-window layer). Per-sequence state need not be a K and a V
cache: a latent-attention layer keeps ONE cache whose rows are neither
(``latent_cache_shape``: the key/value latent beside the shared rotary
key part, padded to whole lane tiles); a state-space block carries a
recurrent state and a convolution tail whose shapes (``ssm_state_shape``,
``conv_tail_shape``, the same owner) do not depend on ``max_len``; a
linear-attention layer carries a state in the same layout. The bodies
declare each piece ONCE, with its kind (``models/decoder.py::state``:
``full``, ``window``, ``latent``, ``ssm``, ``linear``, ``conv``,
``index``), and the generator derives from what its two programs
declared what ``reset()`` zeroes, the ``kv_cache.bytes.<kind>`` gauges
and the slots a decode step may read: what a batch's decode steps NEED
to read of the attended caches (the slots a query may see, once; a
latent row by the lanes its declaration says carry data) is counted on
the host from the positions fed, ``kv_cache.decode_bytes_needed`` over
``kv_cache.decode_steps``. A prefill that takes a block of the batch's
rows writes those rows' FINAL state into the batch's arrays as it writes
their keys and values. A decoder is handed in as an object with
``prefill(ids, batch, max_len, row_ids)``, ``decode_step(token, pos,
max_len)``, ``prefill_rows`` (rows of the batch one prefill dispatch
takes; None for all), ``describe()`` and ``counters_var`` (a device-side
int32 vector the steps update in place, ``len(counter_names)`` long,
zeroed whether or not a step writes it and read once per batch under
``counter_names``; None for none). Both bodies return ``(logits,
extras)``: ``extras`` are further variables of the step (an expert
decoder's selected expert ids) that are fetched beside the logits, so
that whoever checks a step against a reference reads them from the
executables that serve.

After a decoder's body the generator appends the greedy choice to BOTH
programs (``greedy_token``, ops/kv_cache.py): the argmax of the logits
is written to ``NEXT_TOKEN_VAR`` ([batch, 1]) and to the step's column
of ``TOKENS_VAR`` ([batch, max_len - context_len]), two persistables of
the generator's own that ``reset()`` zeroes with the caches. A step's
fetch list is still the logits (and the extras), and ``token_ids`` is
still a feed: ``generate`` feeds the previous step's ``NEXT_TOKEN_VAR``
as the device array it is, dispatches every step with
``return_numpy=False`` (the fetched logits stay on the device and cost
no transfer) and reads ``TOKENS_VAR`` once, after the last step. So the
host never waits on the device inside a batch, and whoever drives the
two programs with host feeds and ``_prefill_fetch`` / ``_decode_fetch``
(a check against a reference, another sampling policy reading the
logits) runs the very executables a request's batch runs.

Generation is O(1) recompute per token instead of O(S): both programs
compile exactly once (shapes never change across steps), so a T-token
generation is 1 prefill dispatch + T-1 decode dispatches against warm
executables. ``generate_full_recompute`` keeps the naive re-run-the-
whole-context baseline alive for parity tests and the bench_serving
speedup measurement.
"""

from __future__ import annotations

import numpy as np

from ..errors import InvalidArgumentError

# the generator's own state beside the decoder's: the token every row
# chose last ([batch, 1], the next step's `token_ids` feed) and all of a
# batch's choices ([batch, max_len - context_len], column t = token t)
NEXT_TOKEN_VAR = "serving_next_token"
TOKENS_VAR = "serving_tokens"


class GPTGenerator:
    """Checkpoint -> tokens through the KV-cache decode path.

    Shapes are fixed at construction (the serving bucket contract):
    `batch` concurrent sequences, `context_len` prompt tokens, caches
    sized `max_len`. ``generate`` emits up to
    ``max_len - context_len`` tokens.
    """

    def __init__(self, cfg, batch, context_len, max_len, scope=None,
                 executor=None):
        import paddle_tpu as fluid
        from .. import observability as _obs
        from ..core.dtypes import to_numpy_dtype
        from ..framework.scope import Scope, scope_guard
        from ..models.decoder import declared_state

        if context_len >= max_len:
            raise InvalidArgumentError(
                f"context_len {context_len} must leave room to generate "
                f"(max_len {max_len})"
            )
        # `cfg`: a decoder, or a GPTConfig (models/gpt.py's decoder)
        if hasattr(cfg, "decode_step"):
            decoder = cfg
        else:
            from ..models.gpt import GPTDecoder

            decoder = GPTDecoder(cfg)
        self.decoder = decoder
        self.cfg = decoder.cfg
        self.batch = int(batch)
        self.context_len = int(context_len)
        self.max_len = int(max_len)
        self.scope = scope or Scope()
        self.executor = executor or fluid.Executor()
        rows = decoder.prefill_rows or self.batch
        if self.batch % rows:
            raise InvalidArgumentError(
                f"prefill_rows {rows} must divide the batch {self.batch}"
            )
        self.prefill_rows = rows

        self.prefill_prog = fluid.Program()
        self.startup_prog = fluid.Program()
        with fluid.program_guard(self.prefill_prog, self.startup_prog):
            ids = fluid.data("context_ids", [rows, context_len], "int64")
            row_ids = None
            if rows != self.batch:
                row_ids = fluid.data("row_ids", [1], "int64")
            logits, extras = decoder.prefill(ids, self.batch, max_len,
                                             row_ids)
            self._append_greedy(logits, row=row_ids)
        self._prefill_fetch = [logits.name] + [v.name for v in extras]

        self.decode_prog = fluid.Program()
        decode_startup = fluid.Program()  # same init ops; never run
        with fluid.program_guard(self.decode_prog, decode_startup):
            tok = fluid.data("token_ids", [batch, 1], "int64")
            pos = fluid.data("pos_ids", [1, 1], "int64")
            dlogits, extras = decoder.decode_step(tok, pos, max_len)
            # the fed token sits at `pos`: the step chooses token
            # pos - context_len + 1 of the batch
            self._append_greedy(dlogits, pos=pos)
        self._decode_fetch = [dlogits.name] + [v.name for v in extras]

        # both are pure inference graphs: mark them so the Executor traces
        # in test mode and the verifier holds the inference contract
        self.prefill_prog._is_inference = True
        self.decode_prog._is_inference = True
        # what the Executor calls the two compiled steps: a capture's
        # device events carry their XLA module, `jit_<family>_prefill` or
        # `jit_<family>_decode`, which is how a reader tells the phases
        family = decoder.describe()["family"]
        self.prefill_prog._label = f"{family}_prefill"
        self.decode_prog._label = f"{family}_decode"
        self._scope_guard = scope_guard
        # the state the two programs declared (models/decoder.py::state),
        # and the counters vector, declared whether or not a step writes it
        declared = declared_state(self.prefill_prog, self.decode_prog)
        if decoder.counters_var is not None:
            declared.setdefault(decoder.counters_var, (
                (len(decoder.counter_names),), "int32", None, None))
        self._state_kinds = {name: spec[2] for name, spec in declared.items()}
        self._state_specs = [
            (name, shape, dtype)
            for name, (shape, dtype, _kind, _lanes) in declared.items()
        ] + [(name, shape, "int64") for name, shape in self._token_vars()]
        by_kind = {}
        # (slots, bytes a slot) of every cache a decode step attends
        # over, kinds "full", "window" (a K and a V array each) and
        # "latent" (one array a layer): what a step at a position needs
        # to read (`_decode_bytes_needed`). A padded row counts the lanes
        # its declaration says carry data.
        self._kv_slots = []
        for shape, dtype, kind, lanes in declared.values():
            if kind:
                itemsize = np.dtype(to_numpy_dtype(dtype)).itemsize
                nbytes = int(np.prod(shape) * itemsize)
                by_kind[kind] = by_kind.get(kind, 0) + nbytes
                if kind in ("full", "window", "latent"):
                    self._kv_slots.append(
                        (shape[1], shape[0] * (lanes or shape[2]) * itemsize))
        for kind, nbytes in by_kind.items():
            _obs.set_gauge(f"kv_cache.bytes.{kind}", nbytes)
        _obs.set_table("serving.generate.model", {
            **decoder.describe(), "batch": self.batch,
            "context_len": self.context_len, "max_len": self.max_len,
        })

    def _token_vars(self):
        return ((NEXT_TOKEN_VAR, (self.batch, 1)),
                (TOKENS_VAR, (self.batch, self.max_len - self.context_len)))

    def _append_greedy(self, logits, pos=None, row=None):
        """The greedy choice of the step being built, left on the device
        in the generator's two token persistables. `pos`: the fed
        token's position (decode); `row`: first row of a prefill block."""
        from ..framework.program import default_main_program, name_scope

        blk = default_main_program().global_block
        nxt, tokens = (
            blk.create_var(name=name, shape=shape, dtype="int64",
                           persistable=True)
            for name, shape in self._token_vars()
        )
        ins = {"Logits": [logits.name], "Tokens": [tokens.name]}
        if pos is not None:
            ins["Pos"] = [pos.name]
        if row is not None:
            # a block's rows go into the batch's array; a whole batch
            # replaces it, so the array a step is fed is never one the
            # step also takes (and donates) as state
            ins.update(Row=[row.name], Next=[nxt.name])
        with name_scope("head"):
            blk.append_op(
                "greedy_token", ins,
                {"TokensOut": [tokens.name], "NextOut": [nxt.name]},
                {"column": 0 if pos is None else 1 - self.context_len},
            )

    def _param_vars(self):
        state = {name for name, _shape, _dtype in self._state_specs}
        return [
            v for v in self.prefill_prog.list_vars()
            if v.persistable and v.name not in state
        ]

    # -- parameters --------------------------------------------------------
    def init_params(self, seed=0):
        """Random-init parameters (bench/test path; production loads a
        checkpoint). Runs the prefill startup program once."""
        self.startup_prog.random_seed = seed
        self.prefill_prog.random_seed = seed
        self.decode_prog.random_seed = seed
        with self._scope_guard(self.scope):
            self.executor.run(self.startup_prog, scope=self.scope)
        self.reset()

    def load_params(self, path):
        """Load trained GPT parameters (``io.save`` format) into the
        shared scope — cache vars excluded (they are runtime state, not
        checkpoint content)."""
        from .. import io as _io

        with self._scope_guard(self.scope):
            _io.load(self.prefill_prog, path, var_list=self._param_vars())
        self.reset()

    def save_params(self, path):
        from .. import io as _io

        with self._scope_guard(self.scope):
            return _io.save(self.prefill_prog, path)

    def reset(self):
        """Zero the generation state by its specs: every layer's KV
        cache or recurrent state, the step counters and the two token
        arrays, each in the
        dtype a host feed of the declared one becomes on the device
        (int64 is int32 unless x64 is on)."""
        import jax
        import jax.numpy as jnp

        from ..core.dtypes import to_numpy_dtype

        for name, shape, dtype in self._state_specs:
            dtype = jax.dtypes.canonicalize_dtype(to_numpy_dtype(dtype))
            self.scope.set_var(name, jnp.zeros(shape, dtype))

    def prefill_feeds(self, ids):
        """The prefill program's feeds for a batch of prompts: one per
        `prefill_rows` rows, each with the rows' offset in the batch."""
        rows = self.prefill_rows
        if rows == self.batch:
            yield {"context_ids": ids}
            return
        for r0 in range(0, self.batch, rows):
            yield {"context_ids": ids[r0:r0 + rows],
                   "row_ids": np.array([r0], np.int64)}

    def _publish_counters(self):
        """The decoder's device-side step counters, read ONCE per batch:
        added to the registry under the decoder's names (a gauge where
        the decoder says the value is no sum) and left on the span as
        args (a window's reader sums the spans that began inside it)."""
        from .. import observability as _obs

        dec = self.decoder
        if dec.counters_var is None:
            return
        with _obs.span("serving.step_counters", category="serving") as sp:
            values = np.asarray(self.scope.find_var(dec.counters_var))
            for name, value in zip(dec.counter_names, values.tolist()):
                sp.args[name] = value
                if name in dec.counter_gauges:
                    _obs.set_gauge(name, value)
                else:
                    _obs.add(name, value)

    def _decode_bytes_needed(self, steps):
        """Bytes of the attention caches (K and V, or a latent layer's
        one) the first `steps` decode steps of a batch need to read: at
        position p a query may see ``min(p + 1, slots)`` slots of a cache
        (a ring's window is its slots), each once."""
        if not self._kv_slots:
            return 0
        slots, slot_bytes = np.array(self._kv_slots).T
        seen = np.arange(1, steps + 1)[:, None] + self.context_len
        return int((np.minimum(seen, slots) * slot_bytes).sum())

    # -- generation --------------------------------------------------------
    def generate(self, context_ids, max_new_tokens, greedy=True):
        """Generate `max_new_tokens` per sequence; returns [B, T] int64.

        Greedy decoding, chosen on the device: each step's program ends
        in the argmax of its logits (first index on a tie, as numpy's
        on the host), the chosen token is the next step's feed as the
        device array the step left in the scope, no step waits for its
        fetch, and the host reads the batch's [B, T] ids once, after the
        last step. A step still fetches its logits (as device arrays
        here): a caller that wants another sampling policy drives
        ``prefill_prog`` / ``decode_prog`` itself with ``_prefill_fetch``
        / ``_decode_fetch`` and its own ``token_ids`` feed, and reads
        the logits."""
        import jax

        from .. import observability as _obs

        if not greedy:
            raise InvalidArgumentError(
                "only greedy decoding is implemented; sample from the "
                "logits fetch at the caller for other policies"
            )
        new = int(max_new_tokens)
        if new < 1:
            raise InvalidArgumentError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        ids = np.asarray(context_ids)
        if ids.shape != (self.batch, self.context_len):
            raise InvalidArgumentError(
                f"context_ids must be [{self.batch}, {self.context_len}], "
                f"got {ids.shape}"
            )
        if self.context_len + new > self.max_len:
            raise InvalidArgumentError(
                f"context {self.context_len} + {max_new_tokens} new tokens "
                f"exceeds max_len {self.max_len}"
            )
        # child spans under the caller's trace (the serving router
        # activates the request's context around runner.run): the
        # reset / prefill / decode split of a generate request's latency —
        # each executor.step inside nests one level further, and each
        # serving.sample is the host's own work between two of them (the
        # next step's feed; the choice itself is the device's)
        with _obs.span("serving.cache_reset", category="serving"):
            self.reset()

        def run(program, feed, fetch):
            self.executor.run(program, feed=feed, fetch_list=fetch,
                              scope=self.scope, return_numpy=False)

        def next_feed(t):
            """The feed of the step that chooses token t + 1."""
            with _obs.span("serving.sample", category="serving"):
                return {
                    "token_ids": self.scope.find_var(NEXT_TOKEN_VAR),
                    "pos_ids": np.array([[self.context_len + t]], np.int64),
                }

        with self._scope_guard(self.scope):
            with _obs.span("serving.prefill", category="serving",
                           context_len=self.context_len):
                for feed in self.prefill_feeds(ids):
                    run(self.prefill_prog, feed, self._prefill_fetch)
                # the span is the prefill's device time: wait for it
                jax.block_until_ready(self.scope.find_var(NEXT_TOKEN_VAR))
            feed = next_feed(0)
            with _obs.span("serving.decode_loop", category="serving",
                           tokens=new):
                for t in range(1, new):
                    run(self.decode_prog, feed, self._decode_fetch)
                    feed = next_feed(t)
                # the batch's one read: returns when the last step has
                # run, so the span holds all of the loop's device work
                out = np.asarray(self.scope.find_var(TOKENS_VAR))
            _obs.add("serving.decode_steps", new - 1)
            _obs.add("kv_cache.decode_steps", new - 1)
            _obs.add("kv_cache.decode_bytes_needed",
                     self._decode_bytes_needed(new - 1))
            self._publish_counters()
        return out[:, :new].astype(np.int64)

    def generate_full_recompute(self, context_ids, max_new_tokens):
        """The naive baseline: re-run the FULL context through a plain
        ``gpt_logits`` graph for every emitted token (one fixed padded
        shape, so it too compiles once — the comparison isolates
        recompute cost, not compile count)."""
        import paddle_tpu as fluid

        if not hasattr(self.decoder, "logits"):
            raise InvalidArgumentError(
                f"{type(self.decoder).__name__} has no full-context graph"
            )
        ids = np.asarray(context_ids)
        if int(max_new_tokens) < 1:
            raise InvalidArgumentError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}"
            )
        t_total = self.context_len + int(max_new_tokens)
        if t_total > self.max_len:
            raise InvalidArgumentError(
                f"context {self.context_len} + {max_new_tokens} new tokens "
                f"exceeds max_len {self.max_len}"
            )
        prog = getattr(self, "_recompute_prog", None)
        if prog is None or self._recompute_len != t_total:
            prog = fluid.Program()
            startup = fluid.Program()  # params come from the shared scope
            with fluid.program_guard(prog, startup):
                full = fluid.data("full_ids", [self.batch, t_total],
                                  "int64")
                logits = self.decoder.logits(full)
            prog._is_inference = True
            self._recompute_prog = prog
            self._recompute_len = t_total
            self._recompute_fetch = [logits.name]
        prog = self._recompute_prog
        padded = np.zeros((self.batch, t_total), np.int64)
        padded[:, : self.context_len] = ids
        out = np.zeros((self.batch, max_new_tokens), np.int64)
        cur = self.context_len
        with self._scope_guard(self.scope):
            for t in range(max_new_tokens):
                (logits,) = self.executor.run(
                    prog, feed={"full_ids": padded},
                    fetch_list=self._recompute_fetch, scope=self.scope,
                )
                nxt = np.argmax(np.asarray(logits)[:, cur - 1, :], axis=-1)
                out[:, t] = nxt
                if cur < t_total:
                    padded[:, cur] = nxt
                cur += 1
        return out


class GPTGenerateRunner:
    """Router runner wrapping a GPTGenerator: a "generate" endpoint whose
    batched dispatch is one prefill + T decode steps. The endpoint bucket
    must equal the generator's batch (cache shapes are static)."""

    def __init__(self, generator, max_new_tokens):
        self.generator = generator
        self.max_new_tokens = int(max_new_tokens)
        self.feed_names = ("context_ids",)

    def validate_config(self, config):
        """Endpoint hook: cache shapes are static, so every configured
        bucket must equal the generator's batch exactly."""
        bad = [b for b in config.buckets if b != self.generator.batch]
        if bad:
            raise InvalidArgumentError(
                f"GPT generate endpoint buckets {config.buckets} must all "
                f"equal the generator batch {self.generator.batch} (cache "
                "shapes are compiled static)"
            )

    def sample_spec(self, name):
        return (self.generator.context_len,), "int64"

    def run(self, feed):
        return [
            self.generator.generate(
                feed["context_ids"], self.max_new_tokens
            )
        ]
