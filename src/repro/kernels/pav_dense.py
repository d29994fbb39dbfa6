"""Gather-free divide-and-conquer PAV: the ``"dense"`` isotonic backend.

The same Pool-Adjacent-Violators fixed point as ``repro.kernels.pav_scan``
— the same ``log2(n)`` merge levels, the same boundary pool per segment
pair and the same absorption order — with every element-wise gather
taken out.  ``scan`` keeps a block's aggregate registers only at the
block's first position and reads them back with ``take_along_axis``; on
TPU such a gather runs at about 10 ns an element, which is the whole cost
of that solve.  Here every position of a block holds the block's
``start``, ``end`` and registers, so at level ``l`` (``m = 2**l``, windows
of ``w = 2m`` positions, one per segment pair):

* the boundary blocks are static slices of the level's window view: the
  left block's ``start`` and registers at window offset ``m - 1``, the
  right block's registers and ``end`` at offset ``m``;
* the absorption loop reads the neighbour at ``pl - 1`` / ``pr + 1`` as a
  masked reduction over the pair's window (exactly one position is
  selected, so the read is exact);
* the write-back broadcasts the pool over its window and selects it at
  every position in ``[pl, pr]``;
* the fitted values are ``block_value(regs)`` position by position.

Each merge applies the same operations to the same values in the same
order as ``scan``, so the output is bitwise equal to it wherever the
compiler rounds those operations alike: always for L2 (additions and one
division, each correctly rounded); for KL's ``logaddexp`` the CPU
compiler's rounding can depend on the shapes around it (``scan`` alone
then differs by a few ulps between a row solved alone and in a batch).
The price is
that every read touches the whole window instead of one element: on CPU,
where gathers are cheap, ``scan`` stays faster, and the built-in plan
routes only TPU here.

Layouts, chosen from the static shape (TPU pads a minor axis to 128
lanes and a second-minor one to 8 sublanes):

* 128 rows or more: the rows go on the lanes, ``(N, B)`` for the whole
  solve, each level viewed as ``(pairs, w, B)`` — windows run down the
  sublanes and the window reads are sublane reductions;
* fewer rows: the row stays lane-dense.  A window narrower than the 128
  lanes is reduced within its aligned group of lanes by a butterfly of
  lane rotations; a wider one is a row of a ``(B, pairs, w)`` view.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels.pav_scan import _next_pow2, _pad_cols

Array = jax.Array

_INT = jnp.int32

# TPU vector lanes: a minor axis narrower than this is padded up to it.
_LANES = 128


def _lowest(dtype) -> float | int:
  if jnp.issubdtype(dtype, jnp.floating):
    return -jnp.inf
  return int(jnp.iinfo(dtype).min)


def _level_layout(b: int, n: int, lvl: int, rows_minor: bool):
  """How level ``lvl`` views its arrays, from the static shape.

  The arrays are (B, N), or (N, B) when ``rows_minor``.  Returns
  ``(shape, pos, seg_lo, window_max, at)``: the view's shape (a reshape);
  each element's position in its row; each window's first position; a
  function giving each window's max; and a function reading window offset
  ``k`` of every window.  Per-window values come back broadcastable
  against the view.
  """
  w = 2 << lvl
  if rows_minor or w >= _LANES or w == n:
    if rows_minor:
      # Many rows: rows on the lanes, (pairs, w, B), windows down the sublanes.
      shape, wax = (n // w, w, b), 1
    else:
      # Windows of at least a lane row: (B, pairs, w), one window per row.
      shape, wax = (b, n // w, w), 2
    pos = (lax.broadcasted_iota(_INT, shape, wax - 1) * w
           + lax.broadcasted_iota(_INT, shape, wax))

    def window_max(x):
      return jnp.max(x, axis=wax, keepdims=True)

    def at(x, k):
      return lax.slice_in_dim(x, k, k + 1, axis=wax)

    return shape, pos, at(pos, 0), window_max, at

  # Narrow windows: keep the row lane-dense as (B, n / lanes, lanes) and
  # reduce within each aligned group of ``w`` lanes by a butterfly: after
  # step k every element holds the max over its group of 2**(k+1).
  lanes = min(n, _LANES)
  shape = (b, n // lanes, lanes)
  lane = lax.broadcasted_iota(_INT, shape, 2)
  pos = lax.broadcasted_iota(_INT, shape, 1) * lanes + lane
  off = lane & (w - 1)

  def window_max(x):
    for k in range(lvl + 1):
      s = 1 << k
      partner = jnp.where((lane & s) != 0, jnp.roll(x, s, axis=2),
                          jnp.roll(x, -s, axis=2))
      x = jnp.maximum(x, partner)
    return x

  def at(x, k):
    return window_max(jnp.where(off == k, x, _lowest(x.dtype)))

  return shape, pos, pos - off, window_max, at


def _merge_level(start, end, regs, lvl, merge, block_value, rows_minor):
  """Merge adjacent solved segments of size 2**lvl.  start/end/regs hold
  each block's values at every position of the block."""
  flat_shape = start.shape
  b, n = flat_shape[::-1] if rows_minor else flat_shape
  m = 1 << lvl
  shape, pos, seg_lo, window_max, at = _level_layout(b, n, lvl, rows_minor)

  def pick(x, hit):
    # Exactly one element of each window is hit; the rest are masked to
    # the lowest value, so the max reads it exactly (-0.0 and infinities
    # included) and nothing else in the window leaks in.
    return window_max(jnp.where(hit, x, _lowest(x.dtype)))

  start, end = start.reshape(shape), end.reshape(shape)
  regs = tuple(r.reshape(shape) for r in regs)
  seg_hi = seg_lo + (2 * m - 1)
  bnd = seg_lo + m

  # Boundary blocks: the left one covers offset m - 1, the right one
  # starts at offset m.
  l_start = at(start, m - 1)
  l_regs = tuple(at(r, m - 1) for r in regs)
  r_regs = tuple(at(r, m) for r in regs)
  viol = block_value(l_regs) < block_value(r_regs)

  # Initial pool = left boundary block + right boundary block.
  pl = jnp.where(viol, l_start, bnd)
  pr = jnp.where(viol, at(end, m), bnd)
  pregs = tuple(jnp.where(viol, m_, r_)
                for m_, r_ in zip(merge(l_regs, r_regs), r_regs))

  def w_cond(state):
    return jnp.any(state[3])

  def w_body(state):
    pl, pr, pregs, live = state
    gamma = block_value(pregs)
    # Left neighbour block of the pool (if the pool is not at seg_lo).
    has_l = live & (pl > seg_lo)
    hit_l = pos == jnp.maximum(pl - 1, seg_lo)
    nb_l_start = pick(start, hit_l)
    nb_l_regs = tuple(pick(r, hit_l) for r in regs)
    absorb_l = has_l & (block_value(nb_l_regs) < gamma)
    # Right neighbour block (starts at pr + 1 when inside the pair).
    has_r = live & (pr < seg_hi)
    hit_r = pos == jnp.minimum(pr + 1, seg_hi)
    nb_r_regs = tuple(pick(r, hit_r) for r in regs)
    nb_r_end = pick(end, hit_r)
    absorb_r = has_r & (gamma < block_value(nb_r_regs))
    # Both absorptions are decided against the same pool value, as in
    # ``scan``: absorbing one side never undoes the other's violation.
    pregs = tuple(jnp.where(absorb_l, m_, p_)
                  for m_, p_ in zip(merge(pregs, nb_l_regs), pregs))
    pl = jnp.where(absorb_l, nb_l_start, pl)
    pregs = tuple(jnp.where(absorb_r, m_, p_)
                  for m_, p_ in zip(merge(pregs, nb_r_regs), pregs))
    pr = jnp.where(absorb_r, nb_r_end, pr)
    return pl, pr, pregs, absorb_l | absorb_r

  pl, pr, pregs, _ = lax.while_loop(w_cond, w_body, (pl, pr, pregs, viol))

  # Write the pool back over every position it covers.
  in_pool = viol & (pl <= pos) & (pos <= pr)
  flat = lambda x: x.reshape(flat_shape)
  start = flat(jnp.where(in_pool, pl, start))
  end = flat(jnp.where(in_pool, pr, end))
  regs = tuple(flat(jnp.where(in_pool, p, r)) for p, r in zip(pregs, regs))
  return start, end, regs


def _dense_pav(
    regs0: tuple[Array, ...],
    merge: Callable[[tuple, tuple], tuple],
    block_value: Callable[[tuple], Array],
) -> Array:
  """Run the gather-free D&C PAV on per-position registers.

  ``regs0``: tuple of (B, N) arrays, N a power of two — the singleton-block
  registers of every position.  Returns the (B, N) fitted values.
  """
  b_rows, n = regs0[0].shape
  rows_minor = b_rows >= _LANES     # the rows fill the lanes
  if rows_minor:
    regs0 = tuple(r.T for r in regs0)
  start = lax.broadcasted_iota(_INT, regs0[0].shape, 0 if rows_minor else 1)
  end = start
  regs = regs0
  for lvl in range(n.bit_length() - 1):
    start, end, regs = _merge_level(start, end, regs, lvl, merge,
                                    block_value, rows_minor)
  out = block_value(regs)
  return out.T if rows_minor else out


@jax.jit
def pav_l2_dense(y: Array) -> Array:
  """Batched isotonic regression (non-increasing) on (B, n), gather-free."""
  dt = jnp.promote_types(y.dtype, jnp.float32)
  yc = y.astype(dt)
  b, n = yc.shape
  if n <= 1 or b == 0:
    return yc.astype(y.dtype)
  # The row-minimum sentinel of ``pav_l2_scan``: it never pools with data.
  pad = jnp.min(yc, axis=1, keepdims=True)
  yp = _pad_cols(yc, _next_pow2(n) - n, pad)
  out = _dense_pav(
      (yp, jnp.ones_like(yp)),
      merge=lambda a, c: (a[0] + c[0], a[1] + c[1]),
      block_value=lambda r: r[0] / jnp.maximum(r[1], 1e-30),
  )
  return out[:, :n].astype(y.dtype)


@jax.jit
def pav_kl_dense(s: Array, w: Array) -> Array:
  """Batched entropic isotonic optimization on (B, n) x (B, n), gather-free."""
  dt = jnp.promote_types(s.dtype, jnp.float32)
  sc, wc = s.astype(dt), w.astype(dt)
  b, n = sc.shape
  if n <= 1 or b == 0:
    return (sc - wc).astype(s.dtype)
  big_n = _next_pow2(n)
  # The sentinel of ``pav_kl_scan``: min(s) - max(w) - log(n) - 1.
  s_pad = jnp.min(sc, axis=1, keepdims=True)
  w_pad = jnp.max(wc, axis=1, keepdims=True) + jnp.log(jnp.asarray(n, dt)) + 1
  out = _dense_pav(
      (_pad_cols(sc, big_n - n, s_pad), _pad_cols(wc, big_n - n, w_pad)),
      merge=lambda a, c: (jnp.logaddexp(a[0], c[0]),
                          jnp.logaddexp(a[1], c[1])),
      block_value=lambda r: r[0] - r[1],
  )
  return out[:, :n].astype(s.dtype)
