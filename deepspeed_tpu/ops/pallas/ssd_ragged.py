"""Segment-aware state-space scan (Mamba-2 "SSD") over the flat rows of a
ragged inference step, with the recurrent state in per-sequence slots.

The rows of a step are its sequences' runs laid end to end (some decode
rows of one token each, then chunks of prompts, then a padded tail); row
``t`` belongs to state slot ``token_slot[t]``.  Per head (state ``S`` in
``R^{P x N}``, one ``B``/``C`` pair per group of heads)::

    S_t = exp(dt_t * A) S_{t-1} + dt_t * x_t B_t^T        y_t = S_t C_t

A run starts from its slot's state, or from zeros where its first row has
``token_pos == 0`` (so a reused slot needs no clearing), and leaves its
last state in the slot.  Rows of the pad slot (the last one) compute
nothing that is kept.  ``D * x``, the gate and the norm are the caller's.

``state`` is one layer's slots ``[S + 1, H, P, N]``, or every layer's
``[L, S + 1, H, P, N]`` with ``layer`` naming the one to use: a layer scan
then carries the whole array and the kernel touches the slots of its
step's runs in it and nothing else (sliced per layer as a scan's ``xs``
and stacked as its ``ys`` the array would be copied every step).

Two formulations of the one function :func:`ssd_ragged`:

* ``ssd_ragged_xla``: plain XLA, the whole step as one chunk (a masked
  ``[T, T]`` decay matrix per head).  It gathers a starting state per ROW,
  ``[T, H, P, N]``: for the CPU and for tests, not for published widths.
* ``ssd_ragged_pallas`` (``pl.pallas_call(name="ssd_ragged")``): the rows
  are cut into aligned chunks of ``chunk`` rows and every chunk into
  PIECES, the rows of one run inside one chunk.  Grid (head blocks,
  pieces): the first piece of a chunk computes the chunk's masked decay
  matrix times ``C B^T`` times ``dt x`` on the MXU for all its rows at
  once (a piece sees only its own run through the segment mask); every
  piece adds ``C_t decay_t S`` for its rows from the state it starts
  from, and ends ``S <- decay S + sum_j decay_j dt_j x_j B_j^T``.  The
  state block of a piece is picked by a scalar-prefetched slot table, so
  the pipeline double-buffers one slot's read against another's compute;
  the state array is aliased in place (``input_output_aliases``) and stays
  float32.  A run that crosses a chunk boundary carries its state in VMEM
  scratch; a piece that starts from zeros or from the carry names the
  previous piece's block and so fetches nothing.  A decode row is a run
  of one and takes the same kernel.  Loops over heads are ``fori_loop``s
  (a Python loop would be unrolled into every program: PERF.md, PR 27).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INTERPRET = False

NEG = -1e30
# heads one program serves: a block of float32 state is HEAD_BLOCK * P * N
# * 4 bytes, held twice for reading and twice for writing by the pipeline
HEAD_BLOCK = 8
_VMEM_LIMIT = 48 * 1024 * 1024

_SKIP, _ZERO, _FIRST_OF_RUN, _FIRST_IN_CHUNK = 1, 2, 4, 8


def run_layout(token_slot, token_pos):
    """Per row of the step: where its run starts, whether that run starts
    from zeros, and whether the row is its run's last."""
    t = token_slot.shape[0]
    rows = jnp.arange(t, dtype=jnp.int32)
    prev = jnp.concatenate([token_slot[:1] - 1, token_slot[:-1]])
    starts = token_slot != prev
    run_start = lax.cummax(jnp.where(starts, rows, 0))
    from_zero = token_pos[run_start] == 0
    nxt = jnp.concatenate([token_slot[1:], token_slot[-1:] - 1])
    return run_start, from_zero, token_slot != nxt


def ssd_ragged_xla(x, dt, a, b, c, state, token_slot, token_pos):
    """x: [T, H, P]; dt: [T, H] float32, after the softplus; a: [H]
    float32, negative; b, c: [T, G, N]; state: [S + 1, H, P, N] float32,
    the last slot the pad's; token_slot, token_pos: [T].  Returns
    (y [T, H, P] float32, state')."""
    t, h, p = x.shape
    g = b.shape[1]
    pad = state.shape[0] - 1
    f32 = jnp.float32
    run_start, from_zero, is_last = run_layout(token_slot, token_pos)
    da = dt * a[None]                                        # [T, H]
    cs = jnp.cumsum(da, axis=0)
    before = jnp.where((run_start > 0)[:, None],
                       cs[jnp.maximum(run_start - 1, 0)], 0.0)
    cs = cs - before                        # decay exponent inside the run
    rows = jnp.arange(t)
    seen = (token_slot[:, None] == token_slot[None, :]) \
        & (rows[None, :] <= rows[:, None])                   # [T(i), T(j)]
    decay = jnp.exp(jnp.where(seen[None], cs.T[:, :, None] - cs.T[:, None, :],
                              NEG))                          # [H, T, T]
    rep = h // g
    cb = jnp.einsum("ign,jgn->gij", c.astype(f32), b.astype(f32))
    m = jnp.repeat(cb, rep, axis=0) * decay                  # [H, T, T]
    xd = x.astype(f32) * dt[..., None]                       # [T, H, P]
    s0 = jnp.where(from_zero[:, None, None, None], 0.0, state[token_slot])
    bh = jnp.repeat(b.astype(f32), rep, axis=1)              # [T, H, N]
    ch = jnp.repeat(c.astype(f32), rep, axis=1)
    into = jnp.exp(cs)[..., None, None] * s0                 # [T, H, P, N]
    s_t = into + jnp.einsum("hij,jhp,jhn->ihpn", decay, xd, bh)
    y = jnp.einsum("hij,jhp->ihp", m, xd) \
        + jnp.einsum("thpn,thn->thp", into, ch)
    dest = jnp.where(is_last, token_slot, pad)
    return y, state.at[dest].set(s_t)


def _kernel(chunk_ref, r0_ref, r1_ref, in_slot_ref, out_slot_ref, flags_ref,
            layer_ref, x_ref, xt_ref, b_ref, c_ref, csc_ref, csr_ref, slc_ref,
            slr_ref, s_in_ref, y_ref, s_out_ref, carry):
    """One piece of one head block.  x_ref: [HB, Q, P] (dt * x);
    xt_ref: [HB, P, Q]; b_ref, c_ref: [1, Q, N]; csc_ref / csr_ref: the
    chunk's running sum of dt * A as a column [HB, Q, 1] and a row
    [HB, 1, Q]; slc_ref / slr_ref: the rows' slots likewise; s_in_ref,
    s_out_ref: [1, 1, HB, P, N]; y_ref: [HB, Q, P] float32."""
    del chunk_ref, in_slot_ref, out_slot_ref, layer_ref
    piece = pl.program_id(1)
    flags = flags_ref[piece]
    r0, r1 = r0_ref[piece], r1_ref[piece]
    hb, q = x_ref.shape[0], x_ref.shape[1]
    f32 = jnp.float32
    mxu = x_ref.dtype
    row = lax.broadcasted_iota(jnp.int32, (q, 1), 0)
    col = lax.broadcasted_iota(jnp.int32, (1, q), 1)

    @pl.when((flags & _FIRST_IN_CHUNK) != 0)
    def _():
        # every row of the chunk against the rows before it in its run
        cb = lax.dot_general(c_ref[0], b_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=f32)      # [Q, Q]
        seen = (slc_ref[...] == slr_ref[...]) & (col <= row)

        def head(h, _):
            decay = jnp.exp(jnp.where(seen, csc_ref[h] - csr_ref[h], NEG))
            y_ref[h] = lax.dot_general(
                (cb * decay).astype(mxu), x_ref[h],
                (((1,), (0,)), ((), ())), preferred_element_type=f32)
            return 0

        lax.fori_loop(0, hb, head, 0)

    @pl.when((flags & _SKIP) == 0)
    def _():
        in_piece = (row >= r0) & (row < r1)                   # [Q, 1]
        from_zero = (flags & _ZERO) != 0

        # a run's first piece starts from zeros or from its slot; any
        # other from what the piece before it left in ``carry``
        @pl.when(from_zero)
        def _():
            carry[...] = jnp.zeros(carry.shape, f32)

        @pl.when(((flags & _FIRST_OF_RUN) != 0) & jnp.logical_not(from_zero))
        def _():
            carry[...] = s_in_ref[0, 0]

        c32 = c_ref[0].astype(f32)
        b32 = b_ref[0].astype(f32)

        def head(h, _):
            cs = csc_ref[h]                                   # [Q, 1]
            # the running sum just before the piece and at its last row
            cs_pre = jnp.sum(jnp.where(row == r0 - 1, cs, 0.0), axis=0,
                             keepdims=True)                   # [1, 1]
            cs_end = jnp.sum(jnp.where(row == r1 - 1, cs, 0.0), axis=0,
                             keepdims=True)
            s = carry[h]                                      # [P, N]
            c_in = c32 * jnp.exp(jnp.where(in_piece, cs - cs_pre, NEG))
            y_ref[h] += lax.dot_general(
                c_in, s, (((1,), (1,)), ((), ())),
                preferred_element_type=f32)                   # [Q, P]
            b_out = b32 * jnp.exp(jnp.where(in_piece, cs_end - cs, NEG))
            s_new = s * jnp.exp(cs_end - cs_pre) + lax.dot_general(
                xt_ref[h], b_out.astype(mxu), (((1,), (0,)), ((), ())),
                preferred_element_type=f32)                   # [P, N]
            carry[h] = s_new
            s_out_ref[0, 0, h] = s_new
            return 0

        lax.fori_loop(0, hb, head, 0)


def _fill_forward(values, valid, default):
    """``values[i]`` where ``valid[i]``, else the last valid one before
    it, else ``default``."""
    idx = jnp.where(valid, jnp.arange(values.shape[0], dtype=jnp.int32), -1)
    last = lax.cummax(idx)
    return jnp.where(last >= 0, values[jnp.maximum(last, 0)], default)


def piece_tables(token_slot, token_pos, q: int, n_pieces: int, pad: int):
    """The step's rows cut at every start of a run and every multiple of
    ``q``: for each of ``n_pieces`` pieces (the real ones first, in row
    order) its chunk, its rows ``[r0, r1)`` inside the chunk, the state
    block it reads and the one it writes, and its flags."""
    t = token_slot.shape[0]
    rows = jnp.arange(t, dtype=jnp.int32)
    run_start, from_zero, _ = run_layout(token_slot, token_pos)
    starts = (run_start == rows) | (rows % q == 0)
    piece_of = jnp.cumsum(starts.astype(jnp.int32)) - 1
    real = jnp.arange(n_pieces, dtype=jnp.int32) <= piece_of[-1]
    first = jnp.full((n_pieces,), t, jnp.int32).at[piece_of].min(
        rows, mode="drop")
    end = jnp.zeros((n_pieces,), jnp.int32).at[piece_of].max(
        rows + 1, mode="drop")
    at = jnp.minimum(first, t - 1)
    chunk = at // q
    r0 = jnp.where(real, first - chunk * q, 0)
    r1 = jnp.where(real, end - chunk * q, 0)
    slot = token_slot[at]
    skip = ~real | (slot == pad)
    of_run = run_start[at] == at
    zero = of_run & from_zero[at]
    flags = (skip * _SKIP + (~skip & zero) * _ZERO
             + (~skip & of_run) * _FIRST_OF_RUN
             + (real & (r0 == 0)) * _FIRST_IN_CHUNK).astype(jnp.int32)
    in_slot = _fill_forward(slot, ~skip & of_run & ~zero, pad)
    out_slot = _fill_forward(slot, ~skip, pad)
    return chunk, r0, r1, in_slot, out_slot, flags


def ssd_ragged_pallas(x, dt, a, b, c, state, token_slot, token_pos,
                      layer=None, chunk: int = 128):
    """The same function as :func:`ssd_ragged_xla`, through the kernel.
    The state array is updated in place: donate it."""
    if layer is None:
        y, state = ssd_ragged_pallas(x, dt, a, b, c, state[None], token_slot,
                                     token_pos, layer=0, chunk=chunk)
        return y, state[0]
    t, h, p = x.shape
    g, n = b.shape[1], b.shape[2]
    pad = state.shape[1] - 1
    f32 = jnp.float32
    # whole chunks: a short step is padded to one (its cost is the
    # slots' state, not the rows)
    q = chunk
    tp = -(-t // q) * q
    if tp != t:
        rows = ((0, tp - t),)
        x = jnp.pad(x, rows + ((0, 0), (0, 0)))
        dt = jnp.pad(dt, rows + ((0, 0),))
        b = jnp.pad(b, rows + ((0, 0), (0, 0)))
        c = jnp.pad(c, rows + ((0, 0), (0, 0)))
        token_slot = jnp.pad(token_slot, rows, constant_values=pad)
        token_pos = jnp.pad(token_pos, rows)
    token_slot = token_slot.astype(jnp.int32)
    n_chunks = tp // q
    # runs are at most the slots (the pad's among them), and every chunk
    # boundary cuts at most one of them
    n_pieces = min(tp, pad + n_chunks)
    hpg = h // g
    hb = next(k for k in range(min(HEAD_BLOCK, hpg), 0, -1) if hpg % k == 0)
    tables = piece_tables(token_slot, token_pos.astype(jnp.int32), q,
                          n_pieces, pad)

    xd = (x.astype(f32) * dt[..., None]).astype(x.dtype)      # [T, H, P]
    cs = jnp.cumsum((dt * a[None]).reshape(n_chunks, q, h), axis=1)
    cs = cs.reshape(tp, h).T                                  # [H, T]

    def by_chunk(lead):
        return lambda hb_, p_, ch, *_: (lead(hb_), ch[p_], 0)

    head_rows = pl.BlockSpec((hb, q, p), by_chunk(lambda k: k))
    state_spec = lambda which: pl.BlockSpec(
        (1, 1, hb, p, n), lambda hb_, p_, ch, r0, r1, si, so, fl, ly:
        (ly[0], (si, so)[which][p_], hb_, 0, 0))
    group_rows = pl.BlockSpec((1, q, n), by_chunk(lambda k: k * hb // hpg))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(h // hb, n_pieces),
        in_specs=[
            head_rows,                                        # dt * x
            pl.BlockSpec((hb, p, q),
                         lambda hb_, p_, ch, *_: (hb_, 0, ch[p_])),
            group_rows, group_rows,                           # B, C
            pl.BlockSpec((hb, q, 1), by_chunk(lambda k: k)),
            pl.BlockSpec((hb, 1, q),
                         lambda hb_, p_, ch, *_: (hb_, 0, ch[p_])),
            pl.BlockSpec((q, 1), lambda hb_, p_, ch, *_: (ch[p_], 0)),
            pl.BlockSpec((1, q), lambda hb_, p_, ch, *_: (0, ch[p_])),
            state_spec(0),
        ],
        out_specs=[head_rows, state_spec(1)],
        scratch_shapes=[pltpu.VMEM((hb, p, n), f32)],
    )
    xh = xd.swapaxes(0, 1)                                    # [H, T, P]
    y, state = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((h, tp, p), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 15 (after the six tables and the layer): the state,
        # written in place
        input_output_aliases={15: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=INTERPRET,
        name="ssd_ragged",
    )(*tables, jnp.asarray(layer, jnp.int32).reshape(1), xh,
      xh.swapaxes(1, 2), b.swapaxes(0, 1), c.swapaxes(0, 1),
      cs[:, :, None], cs[:, None, :], token_slot[:, None],
      token_slot[None, :], state)
    return y.swapaxes(0, 1)[:t], state


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def ssd_ragged(x, dt, a, b, c, state, token_slot, token_pos, layer=None,
               impl: str = "xla", chunk: int = 128):
    """``impl``: ``"pallas"`` (the kernel) or ``"xla"``; ``layer``: which
    of a five-dimensional ``state``'s layers (None: ``state`` is one)."""
    if impl == "pallas":
        return ssd_ragged_pallas(x, dt, a, b, c, state, token_slot,
                                 token_pos, layer=layer, chunk=chunk)
    if impl != "xla":
        raise ValueError(f"ssd_ragged: unknown impl {impl!r} (pallas|xla)")
    if layer is None:
        return ssd_ragged_xla(x, dt, a, b, c, state, token_slot, token_pos)
    y, one = ssd_ragged_xla(x, dt, a, b, c, state[layer], token_slot,
                            token_pos)
    return y, state.at[layer].set(one)
