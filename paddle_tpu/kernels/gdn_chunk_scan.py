"""A prefill's gated delta rule, chunk by chunk, everything a chunk makes
kept in VMEM.

What `ops/ssm.py::gated_delta_chunked` computes (its docstring has the
mathematics; it stays as the CPU path and as this kernel's reference),
forward only, for a dispatch's rows from a zero state or one carried in:

    L = strictly_lower((beta K) K^T * Gamma);  T = (I + L)^-1
    V' = T (beta V - (beta K e^G) S)
    O = (Q e^G) S + lower(Q K^T * Gamma) V'
    S <- e^{G_last} S + (K e^{G_last - G})^T V'

The XLA form makes every one of these a pass over HBM: the `[C, C]`
solve of each chunk and head goes through twelve whole-array float32
products, the chunked operands are transposed on the way in and out, and
the scan reads W, U and the decayed q and k back (PERF.md, Findings
PR 38: 847 ms of a 1,875 ms prefill). Here one grid step owns one row,
`HEADS_A_STEP` key heads with their value heads, and one chunk; the chunk
axis is the grid's last and "arbitrary", and the state of the step's
value heads, `[dk, dv]` float32 each, lives in the OUTPUT block of the
final state, which is resident while the chunks go by and leaves for HBM
once, after the last. A value head's chunk is a chain of small dependent
operations, so the step's heads are traced a stage of each in turn
(`_kernel`): side by side they hide each other's latencies.

Layouts: q, k, v are read in place out of the convolution's one array
`[R, L, 2 Hk dk + Hv dv]` by lane-block index (a head is a whole number
of 128-lane tiles), o is written straight into `[R, L, Hv dv]`, and the
final state `[R, Hv, dk, dv]` IS the stored layout
(`ops/kv_cache.py::ssm_state_shape(B, Hv, dv, dk, Hk)` at pack 1: the key
dimension on the sublanes). g and beta are small (`[R, L, Hv]`): the
wrapper hands over g's running sum inside each chunk with the positions
on the sublanes (`cols`, with beta and the chunk's last sum beside it)
and on the lanes (`rows`), so that Gamma and every row scaling are
broadcasts, never a transposition. q and k are L2-normalised in the
kernel.

The solve (PERF.md, Findings PR 39 has the forms that were timed): the
diagonal blocks of 8 rows by substitution on the vector unit, ALL of a
chunk's blocks in one vreg (a block's row on the sublanes, the matrix
column on the lanes: a step is a lane rotation, a multiply and a subtract
of single vregs); then `unit_lower_inverse`'s doubling, `inv - inv B
inv`, from 8 rows up to the chunk on tiles that never leave VMEM, only the
rows of the odd blocks (the others are zero in `inv B inv`) going
through the matrix unit.

Precision is the `jnp` form's: a product of two activations (K K^T,
Q K^T, scores V', K^T V') takes them in the activations' dtype and
accumulates in float32; the solve and every product that reads T or the
state are float32 (`Precision.HIGHEST`); the state, g, beta, the norms
and every decay are float32. One regrouping: V' = T (beta V - W' S) with
W' = beta K e^G, where the `jnp` form multiplies T into both terms
first; (W'; Q e^G) S is ONE product of 2 C rows against the state.

Not taken (`supports`): head widths that are not whole 128-lane tiles,
value heads that the key heads do not divide, a chunk that is no power
of two of at least 16 rows (a bfloat16 tile), a dtype other than float32
and bfloat16, value heads whose lanes do not start on a block of the
step's width. The op then runs the `jnp` form.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
LANES = 128
EXACT = jax.lax.Precision.HIGHEST
# rows of a diagonal block inverted on the vector unit before the
# doubling takes over on the matrix unit: a vreg's sublanes
SUB = 8
_SUB_BITS = SUB.bit_length() - 1
# key heads a grid step owns at most: their value heads' chains are woven
# (see `_kernel`): 9.24 / 6.50 / 4.96 / 4.05 ms a call at 1 / 2 / 4 / 8 in
# the Qwen3-Next cell's shape, and a program's lowering grows with them
HEADS_A_STEP = 8
VMEM_LIMIT_BYTES = 48 * 2 ** 20


def heads_a_step(key_heads, value_heads, key_dim, value_dim):
    """Key heads a grid step owns: the most, up to `HEADS_A_STEP`, that
    divide the key heads and put the first value head's lanes on a whole
    block of the step's value lanes (0: none does)."""
    e = value_heads // key_heads
    for hb in range(min(HEADS_A_STEP, key_heads), 0, -1):
        if key_heads % hb == 0 and \
                (2 * key_heads * key_dim) % (hb * e * value_dim) == 0:
            return hb
    return 0


def supports(key_heads, value_heads, key_dim, value_dim, chunk, dtype):
    """Does the kernel take this call? Shapes and attributes only."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    if key_dim % LANES or value_dim % LANES or value_heads % key_heads:
        return False
    if chunk < 16 or chunk & (chunk - 1):
        return False
    return heads_a_step(key_heads, value_heads, key_dim, value_dim) > 0


def _unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + 1e-6)


def _dot(a, b, dims=((1,), (0,)), precision=None):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)


def _masks(c):
    """What the solve selects by, made once a grid step: of a `[C, C]`
    tile the lower triangle, the strict one and the blocks that join two
    diagonal blocks of b rows (b = 8, 16, ... C / 2); of the `[8, C]`
    vreg that holds every diagonal block, a block's lanes, a block's
    column i, and the identity."""
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    joins, b = {}, SUB
    while b < c:
        shift = b.bit_length() - 1
        joins[b] = ((row >> (shift + 1)) == (col >> (shift + 1))) \
            & (((row >> shift) & 1) == 1) & (((col >> shift) & 1) == 0)
        b *= 2
    lane = jax.lax.broadcasted_iota(jnp.int32, (SUB, c), 1)
    local = jax.lax.broadcasted_iota(jnp.int32, (SUB, c), 0)
    return dict(
        lower=row >= col, strict=row > col, joins=joins,
        block=[(lane >> _SUB_BITS) == blk for blk in range(c // SUB)],
        column=[(lane & (SUB - 1)) == i for i in range(SUB)],
        eye=(local == (lane & (SUB - 1))).astype(F32),
    )


def _solve(strict, masks):
    """(I + strict)^-1 of a `[C, C]` strictly lower triangle: a
    generator, one stage a `next`, that returns the inverse."""
    c = strict.shape[0]
    # the diagonal blocks of 8 rows, all of them in ONE vreg: `down` and
    # `inv` are [8, C] with a block's row on the sublanes and the matrix
    # column on the lanes, down[j, col] = L[8 block(col) + j, col]
    down = jnp.zeros((SUB, c), F32)
    for blk, lanes in enumerate(masks["block"]):
        down = jnp.where(lanes, strict[blk * SUB:(blk + 1) * SUB, :], down)
    inv = masks["eye"]
    # back substitution by columns (T (I + L) = I): a block's column i,
    # once final, is taken off the columns before it, L[i, col] times
    for i in range(SUB - 1, 0, -1):
        final = jnp.where(masks["column"][i], inv, 0.0)
        moved = [pltpu.roll(final, c - d, axis=1) for d in range(1, i + 1)]
        while len(moved) > 1:       # to columns i - 1 .. 0, summed by pairs
            moved = [functools.reduce(jnp.add, moved[j:j + 2])
                     for j in range(0, len(moved), 2)]
        inv = inv - jnp.broadcast_to(down[i:i + 1, :], (SUB, c)) * moved[0]
        yield
    inv = jnp.concatenate([jnp.where(lanes, inv, 0.0)
                           for lanes in masks["block"]], axis=0)
    for b, joins in masks["joins"].items():
        below = jnp.where(joins, strict, 0.0)
        # `inv B inv` is zero outside the rows of the odd blocks: they
        # alone go through the matrix unit (whole vregs: b >= 8)
        blocks = [inv[i:i + b] for i in range(0, c, b)]
        odd = jnp.concatenate(blocks[1::2], axis=0)
        odd = odd - _dot(_dot(odd, below, precision=EXACT), inv,
                         precision=EXACT)
        blocks[1::2] = [odd[i:i + b] for i in range(0, c // 2, b)]
        inv = jnp.concatenate(blocks, axis=0)
        yield
    return inv


def _kernel(q_ref, k_ref, v_ref, cols_ref, rows_ref, *refs, hb, e, dk, dv,
            carried):
    first_ref = refs[0] if carried else None
    o_ref, s_ref = refs[-2:]
    chunk = q_ref.shape[0]
    lo = q_ref.dtype
    n = hb * e                      # value heads of the step

    @pl.when(pl.program_id(2) == 0)
    def _():
        s_ref[...] = first_ref[...] if carried \
            else jnp.zeros(s_ref.shape, F32)

    masks = _masks(chunk)
    cols, rows = cols_ref[...], rows_ref[...]
    run, beta, last = (cols[:, j * n:(j + 1) * n] for j in range(3))   # [C, n]
    grow = jnp.exp(run)                             # e^G
    fade = jnp.exp(last - run)                      # e^{G_last - G}
    keep = jnp.exp(last)                            # e^{G_last}, every row

    def wide(x, i, width):          # column i of x over `width` lanes
        return jnp.broadcast_to(x[:, i:i + 1], (chunk, width))

    def key_head(h):
        """Normalised q and k of key head h, and the two score tiles
        its value heads share."""
        q = _unit(q_ref[:, h * dk:(h + 1) * dk].astype(F32)) * dk ** -0.5
        k = _unit(k_ref[:, h * dk:(h + 1) * dk].astype(F32))
        k_lo = k.astype(lo)
        both = _dot(jnp.concatenate([q.astype(lo), k_lo], axis=0), k_lo,
                    ((1,), (1,)))
        return q, k, both[:chunk], both[chunk:]     # qk, kk: [C, C]

    shared = [key_head(h) for h in range(hb)]

    def value_head(i):
        """Value head i of the step through the chunk: a generator that
        yields between its stages."""
        q, k, qk, kk = shared[i // e]
        seg = wide(run, i, chunk) - jnp.broadcast_to(
            rows[i:i + 1, :], (chunk, chunk))       # G_row - G_col
        gamma = jnp.exp(jnp.where(masks["lower"], seg, -jnp.inf))
        strict = jnp.where(masks["strict"],
                           kk * gamma * wide(beta, i, chunk), 0.0)
        yield
        solve = yield from _solve(strict, masks)
        s = s_ref[i]                                # [dk, dv]
        # (beta K e^G ; Q e^G) S: the state is read as the float32 it
        # is, once for both
        into = jnp.concatenate(
            [k * wide(beta * grow, i, dk), q * wide(grow, i, dk)], axis=0)
        read = _dot(into, s, precision=EXACT)       # [2 C, dv]
        yield
        v = v_ref[:, i * dv:(i + 1) * dv].astype(F32)
        fresh = _dot(solve, v * wide(beta, i, dv) - read[:chunk],
                     precision=EXACT)
        fresh_lo = fresh.astype(lo)
        yield
        o = read[chunk:] + _dot((qk * gamma).astype(lo), fresh_lo)
        o_ref[:, i * dv:(i + 1) * dv] = o.astype(o_ref.dtype)
        k_out = (k * wide(fade, i, dk)).astype(lo)
        # e^{G_last} over [dk, dv]: a column of equal rows over the lanes,
        # laid under itself (Mosaic broadcasts ONE element over lanes or
        # sublanes, not both)
        kept = jnp.concatenate(
            [wide(keep, i, dv)] * -(-dk // chunk), axis=0)[:dk]
        s_ref[i] = kept * s + _dot(k_out, fresh_lo, ((0,), (0,)))

    # The step's value heads are independent chains of small dependent
    # operations (seven substitution steps, six products of the doubling,
    # four more behind them): traced one head after the other, the
    # compiler runs them one after the other, each waiting on its own
    # results; traced a stage of every head in turn, it finds them side
    # by side (PERF.md, Findings PR 39: 8.94 -> 4.80 ms a call)
    for _ in itertools.zip_longest(*(value_head(i) for i in range(n))):
        pass


def scan(qkv, g, beta, state=None, *, key_heads, value_heads, key_dim,
         value_dim, chunk, interpret=False):
    """qkv [R, L, 2 Hk dk + Hv dv] (q | k | v after the convolution, in
    the activations' dtype, not yet normalised), g (the log of the decay)
    and beta [R, L, Hv] float32, `state` [R, Hv, dk, dv] float32 or None
    (zeros) -> (o [R, L, Hv dv] in qkv's dtype, the state after row
    L - 1 [R, Hv, dk, dv] float32). `supports` must hold; L need not be
    a multiple of `chunk`."""
    return _call(qkv, g.astype(F32), beta.astype(F32), state,
                 hk=int(key_heads), hv=int(value_heads), dk=int(key_dim),
                 dv=int(value_dim), chunk=int(chunk), interpret=interpret)


# The pallas_call sits in a jit of its own: a model's layers share shapes
# and statics, so a prefill traces and lowers the kernel once, not once a
# layer (kernels/flash_tiled.py, PR 30)
@functools.partial(jax.jit, static_argnames=(
    "hk", "hv", "dk", "dv", "chunk", "interpret"))
def _call(qkv, g, beta, state, *, hk, hv, dk, dv, chunk, interpret):
    r, length, _ = qkv.shape
    e = hv // hk
    hb = heads_a_step(hk, hv, dk, dv)
    n = hb * e
    pad = -length % chunk
    if pad:
        # a padded row has beta = 0 and g = 0: it changes nothing
        qkv, g, beta = (jnp.pad(x, [(0, 0), (0, pad), (0, 0)])
                        for x in (qkv, g, beta))
    nc = (length + pad) // chunk

    def by_step(x):     # [R, L, Hv] -> [R, Hk / hb, nc, C, n]
        return x.reshape(r, nc, chunk, hk // hb, n).transpose(0, 3, 1, 2, 4)

    run = by_step(jnp.cumsum(g.reshape(r, nc, chunk, hv), axis=2))
    rows = jnp.swapaxes(run, 3, 4)                          # [.., n, C]
    # beside it beta and the chunk's last sum, in every row of the chunk
    cols = jnp.concatenate(
        [run, by_step(beta), jnp.broadcast_to(run[..., -1:, :], run.shape)],
        axis=-1).reshape(r, hk // hb, nc * chunk, 3 * n)

    def lanes(width, first):
        return pl.BlockSpec((None, chunk, width),
                            lambda i, h, c: (i, c, first + h))

    heads = pl.BlockSpec((None, n, dk, dv), lambda i, h, c: (i, h, 0, 0))
    carried = state is not None
    o, final = pl.pallas_call(
        functools.partial(_kernel, hb=hb, e=e, dk=dk, dv=dv,
                          carried=carried),
        name="gdn_chunk_scan",
        grid=(r, hk // hb, nc),
        in_specs=[
            lanes(hb * dk, 0), lanes(hb * dk, hk // hb),
            lanes(n * dv, 2 * hk * dk // (n * dv)),
            pl.BlockSpec((None, None, chunk, 3 * n),
                         lambda i, h, c: (i, h, c, 0)),
            pl.BlockSpec((None, None, None, n, chunk),
                         lambda i, h, c: (i, h, c, 0, 0)),
        ] + [heads] * carried,
        out_specs=[lanes(n * dv, 0), heads],
        out_shape=[
            jax.ShapeDtypeStruct((r, nc * chunk, hv * dv), qkv.dtype),
            jax.ShapeDtypeStruct((r, hv, dk, dv), F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=pltpu.InterpretParams() if interpret else False,
    )(qkv, qkv, qkv, cols, rows, *([state.astype(F32)] if carried else []))
    return o[:, :length], final
