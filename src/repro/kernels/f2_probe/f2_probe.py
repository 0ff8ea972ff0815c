"""Pallas TPU kernels for the F2 read and write hot paths.

Three kernels:

  * `probe` — the original first-hop kernel (slot hash -> index gather ->
    RC decode), index-tiled.  Only the kernel tests call it; its vector
    gather does not lower for the TPU, so no store path selects it.
  * `fused_probe` — the full probe engine: per key, slot hash -> hot-index
    lookup -> bounded chain walk with a per-lane address lower bound
    (resolving records from the log ring *or* the read cache via
    RC-tagged addresses) -> value/meta resolution, emitting
    (found, addr, heads, value, meta, hops, ios, exhausted) in one pass.
    The optional `target` input adds compaction's zero-I/O liveness fast
    path (`head == addr`) as an in-kernel predicate.
  * `fused_write` — the write engine: one pass per mutate batch that
    linearizes per key (first position, last set, RMW accumulation),
    runs the locate walk with RC skip, classifies in-place vs RCU against
    the mutable boundary, and emits the append/index-publish plan
    (`core.write_engine.WritePlan`).  The whole batch is one call —
    intra-batch grouping needs every lane visible.

How the kernels lower (Mosaic has no general vector gather): every lane
is a scalar loop iteration.  Per-lane inputs and outputs live in SMEM,
where scalar loads and stores take any index.  The index, log and
read-cache columns live in VMEM as [N/128, 128] rows; one word is read by
loading its row with a dynamic sublane offset and reducing the lane it
sits in to a scalar (`_fetch`).  The write kernel groups lanes by key and
by hash slot with open-addressing tables in SMEM scratch, so its work is
O(B) where the jnp reference (`ref.fused_write_body`) uses B x B masks.
Both kernels are bit-exact with the references in `ref.py`
(tests/test_probe_engine.py, tests/test_write_engine.py).

The columns are copied whole into VMEM on every call, so a store whose
columns exceed `VMEM_BUDGET_BYTES` cannot use these kernels; the engines
check `probe_vmem_bytes` / `probe_smem_bytes` / `write_smem_bytes`
before selecting them (tests/test_tpu_compile.py compiles both kernels
for a v5e at the largest admitted shapes).  I/O accounting mirrors
`core.chain.walk`: every live hop below `head_boundary` is one modeled
4 KiB random block read; the rest are memory-tier touches.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import (META_INVALID, META_TOMBSTONE, NULL_ADDR, OP_DELETE, OP_RMW,
                  OP_UPSERT, RC_FLAG, _mix)

LANES = 128                 # words per VMEM row
# v5e has 128 MiB of VMEM per core.  A call with no grid holds each input
# once; the v5e compiler accepts ~90 MiB of such columns and refuses
# ~130 MiB.  Half the VMEM is left to XLA's own fusions.
# tests/test_tpu_compile.py compiles both kernels at this budget.
VMEM_BUDGET_BYTES = 64 * 1024 * 1024
# SMEM is 1 MiB on v5e; the per-lane arrays and the write kernel's
# grouping tables must share it with the compiler's own scalars (a
# 4096-lane write, 512 KiB, compiles; an 8192-lane one, 1 MiB, does not).
SMEM_BUDGET_BYTES = 768 * 1024


def _rows_words(n: int) -> int:
    """int32 words a [n] column takes as VMEM rows ((8, 128) tiles)."""
    return -(-n // (8 * LANES)) * 8 * LANES


def probe_vmem_bytes(C: int, R: int, V: int, n_index: int) -> int:
    """VMEM the probe kernel holds: log + read-cache columns, plus the
    hot index when it fuses the slot lookup (`n_index` = 0 otherwise)."""
    words = (3 * _rows_words(C) + _rows_words(C * V)
             + 3 * _rows_words(R) + _rows_words(R * V))
    if n_index:
        words += _rows_words(n_index)
    return 4 * words


def _pad_lanes(B: int) -> int:
    return -(-B // LANES) * LANES


def _table_size(B: int) -> int:
    t = 1
    while t < 2 * B:
        t *= 2
    return t


def probe_smem_bytes(B: int, V: int) -> int:
    """SMEM of one probe call: 5 lane inputs, 7 lane outputs, values."""
    return 4 * _pad_lanes(B) * (12 + V)


def write_smem_bytes(B: int, V: int) -> int:
    """SMEM of one write call: lane inputs and outputs, the per-group
    accumulators and the two grouping tables."""
    Bp = _pad_lanes(B)
    return 4 * (Bp * (2 + 18 + 2 + 3 * V) + 2 * _table_size(Bp) + 4)


def as_rows(x):
    """int32 [N] or [N, V] -> [ceil(N*V/128), 128] rows (zero padded).  A
    value column goes in word-major order (word v of record i at v*N + i):
    [N, V] with small V is stored column-major on the TPU, so its
    transpose flattens without a relayout."""
    flat = (x.T if x.ndim == 2 else x).reshape(-1)
    n = flat.shape[0]
    pad = _rows_words(n) - n
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, LANES)


def _fetch(ref, idx):
    """One int32 word at flat index `idx` of a VMEM rows ref: load its row
    at a dynamic sublane offset, reduce the lane it sits in to a scalar."""
    row = ref[pl.ds(idx >> 7, 1), :]
    lane = lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return jnp.sum(jnp.where(lane == (idx & (LANES - 1)), row, 0))


def _slot(key, E: int):
    return (_mix(key) & jnp.uint32(E - 1)).astype(jnp.int32)


def _is_rc(addr):
    return (addr >= 0) & ((addr & RC_FLAG) != 0)


def _i32(b):
    return b.astype(jnp.int32)


def _walk(key, head, lo, act, fast, hb, cols, *, C, R, V, chain_max,
          rc_match, has_rc):
    """One lane's bounded chain walk, the scalar form of
    `ref.fused_probe_body`.  Returns (found, faddr, hops, ios, exhausted,
    meta, value words)."""
    lk, lp, lm, lv, rk, rp, rm, rv = cols
    null = jnp.int32(NULL_ADDR)

    def in_range(cur):
        return jnp.where(_is_rc(cur), cur != null, (cur != null) & (cur >= lo))

    def log_idx(addr):
        return jnp.maximum(jnp.where(_is_rc(addr), null, addr), 0) & (C - 1)

    def rc_idx(addr):
        return jnp.maximum(addr & ~jnp.int32(RC_FLAG), 0) & (R - 1)

    def record(cur):
        def from_log():
            i = log_idx(cur)
            return _fetch(lk, i), _fetch(lp, i), _fetch(lm, i)

        if not has_rc:
            return from_log()

        def from_rc():
            i = rc_idx(cur)
            return _fetch(rk, i), _fetch(rp, i), _fetch(rm, i)

        return lax.cond(_is_rc(cur), from_rc, from_log)

    def cond(carry):
        h, cur, done, _, _, _ = carry
        return (h < chain_max) & act & ~done & in_range(cur)

    def body(carry):
        h, cur, done, faddr, hops, ios = carry
        k, p, m = record(cur)
        cur_rc = _is_rc(cur)
        match = ((m & META_INVALID) == 0) & (k == key)
        if not rc_match:
            match = match & ~cur_rc
        ios = ios + _i32(~cur_rc & (cur < hb))
        return (h + 1, jnp.where(match, cur, p), match,
                jnp.where(match, cur, faddr), hops + 1, ios)

    zero = jnp.int32(0)
    init = (zero, head, fast, jnp.where(fast, head, null), zero, zero)
    _, cur, done, faddr, hops, ios = lax.while_loop(cond, body, init)
    exhausted = act & ~done & in_range(cur)
    found = done & act

    def resolve():
        def from_log():
            i = log_idx(faddr)
            return (_fetch(lm, i),) + tuple(_fetch(lv, v * C + i)
                                            for v in range(V))

        if not has_rc:
            return from_log()

        def from_rc():
            i = rc_idx(faddr)
            return (_fetch(rm, i),) + tuple(_fetch(rv, v * R + i)
                                            for v in range(V))

        return lax.cond(_is_rc(faddr), from_rc, from_log)

    out = lax.cond(found, resolve, lambda: (zero,) * (V + 1))
    return found, faddr, hops, ios, exhausted, out[0], out[1:]


# ---------------------------------------------------------------------------
# First-hop probe (legacy kernel, index-tiled; kernel tests only)
# ---------------------------------------------------------------------------

def _probe_kernel(keys_ref, index_ref, addr_ref, isrc_ref, *,
                  e_tile: int, index_size: int):
    ei = pl.program_id(1)

    @pl.when(ei == 0)
    def _init():
        addr_ref[...] = jnp.full_like(addr_ref, -1)
        isrc_ref[...] = jnp.zeros_like(isrc_ref)

    keys = keys_ref[...]
    slot = (_mix(keys) & jnp.uint32(index_size - 1)).astype(jnp.int32)
    local = slot - ei * e_tile
    hit = (local >= 0) & (local < e_tile)
    entry = index_ref[jnp.where(hit, local, 0)]
    is_rc = (entry >= 0) & ((entry & RC_FLAG) != 0)
    untagged = jnp.where(entry >= 0, entry & ~jnp.int32(RC_FLAG), entry)
    addr_ref[...] = jnp.where(hit, untagged, addr_ref[...])
    isrc_ref[...] = jnp.where(hit, is_rc.astype(jnp.int32), isrc_ref[...])


def probe(keys, index_addr, *, b_tile: int = 1024, e_tile: int = 1 << 16,
          interpret: bool = False):
    """keys: [B] int32; index_addr: [E] int32 chain heads.
    Returns (addr [B] int32 untagged, is_rc [B] int32)."""
    B = keys.shape[0]
    E = index_addr.shape[0]
    b_tile = min(b_tile, B)
    e_tile = min(e_tile, E)
    assert B % b_tile == 0 and E % e_tile == 0
    kernel = functools.partial(_probe_kernel, e_tile=e_tile, index_size=E)
    return pl.pallas_call(
        kernel,
        grid=(B // b_tile, E // e_tile),
        in_specs=[
            pl.BlockSpec((b_tile,), lambda bi, ei: (bi,)),
            pl.BlockSpec((e_tile,), lambda bi, ei: (ei,)),
        ],
        out_specs=[
            pl.BlockSpec((b_tile,), lambda bi, ei: (bi,)),
            pl.BlockSpec((b_tile,), lambda bi, ei: (bi,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.int32),
        ],
        interpret=interpret,
    )(keys, index_addr)


# ---------------------------------------------------------------------------
# Fused probe engine (slot hash -> chain walk -> RC check -> value)
# ---------------------------------------------------------------------------

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)
_VMEM = pl.BlockSpec(memory_space=pltpu.VMEM)


def _fused_kernel(keys_ref, heads_ref, lower_ref, active_ref, target_ref,
                  hb_ref, lk, lv, lp, lm, rk, rv, rp, rm,
                  found_ref, addr_ref, heads_out_ref, val_ref, meta_ref,
                  hops_ref, ios_ref, exh_ref, *,
                  B: int, C: int, R: int, V: int, E: int, chain_max: int,
                  rc_match: bool, has_rc: bool, probe_index: bool,
                  has_target: bool):
    cols = (lk, lp, lm, lv, rk, rp, rm, rv)
    hb = hb_ref[0]

    def lane(i, carry):
        key = keys_ref[i]
        act = active_ref[i] != 0
        head = (_fetch(heads_ref, _slot(key, E)) if probe_index
                else heads_ref[i])
        fast = act & (head == target_ref[i]) if has_target else act & False
        found, faddr, hops, ios, exh, meta, value = _walk(
            key, head, lower_ref[i], act, fast, hb, cols, C=C, R=R, V=V,
            chain_max=chain_max, rc_match=rc_match, has_rc=has_rc)
        found_ref[i] = _i32(found)
        addr_ref[i] = faddr
        heads_out_ref[i] = head
        meta_ref[i] = meta
        hops_ref[i] = hops
        ios_ref[i] = ios
        exh_ref[i] = _i32(exh)
        for v in range(V):
            val_ref[i * V + v] = value[v]
        return carry

    lax.fori_loop(0, B, lane, 0)


def fused_probe(keys, heads_src, lower, active, head_boundary,
                log_key, log_val, log_prev, log_meta,
                rc_key, rc_val, rc_prev, rc_meta, *,
                chain_max: int, rc_match: bool = True, has_rc: bool = True,
                probe_index: bool = True, target=None,
                interpret: bool = False, site: str = "probe"):
    """Fused probe over a key batch.  Per-lane arrays are int32 [B]
    (`active` a 0/1 mask, `head_boundary` int32 [1]); the columns are the
    store's [C] / [C, V] log and [R] / [R, V] read-cache arrays (ring
    capacities, powers of two), laid out as VMEM rows here; `heads_src`
    is the hot index [E] (probe_index) or the per-lane heads [B].
    Returns (found, addr, heads, value [B*V], meta, hops, ios, exhausted),
    every element int32.  The call carries `{"f2_site": site}` as its
    kernel metadata, so a profile tells the engine's call sites apart."""
    B = keys.shape[0]
    C, R = log_key.shape[0], rc_key.shape[0]
    V = log_val.shape[1]
    E = heads_src.shape[0]
    assert (C & (C - 1)) == 0 and (R & (R - 1)) == 0
    has_target = target is not None
    if target is None:
        target = jnp.full((B,), NULL_ADDR, jnp.int32)   # never compared
    kernel = functools.partial(
        _fused_kernel, B=B, C=C, R=R, V=V, E=E, chain_max=chain_max,
        rc_match=rc_match, has_rc=has_rc, probe_index=probe_index,
        has_target=has_target)
    heads_in = as_rows(heads_src) if probe_index else heads_src
    lane = jax.ShapeDtypeStruct((B,), jnp.int32)
    return pl.pallas_call(
        kernel,
        in_specs=[_SMEM, _VMEM if probe_index else _SMEM, _SMEM, _SMEM,
                  _SMEM, _SMEM] + [_VMEM] * 8,
        out_specs=[_SMEM] * 8,
        out_shape=[lane, lane, lane,
                   jax.ShapeDtypeStruct((B * V,), jnp.int32),
                   lane, lane, lane, lane],
        interpret=interpret,
        metadata={"f2_site": site},
    )(keys, heads_in, lower, active, target, head_boundary,
      *(as_rows(c) for c in (log_key, log_val, log_prev, log_meta,
                             rc_key, rc_val, rc_prev, rc_meta)))


# ---------------------------------------------------------------------------
# Fused write engine (linearize -> locate -> classify -> plan)
# ---------------------------------------------------------------------------

def _find(tab_ref, T: int, h0, same):
    """Open-addressing probe from bucket h0: the first bucket that is
    empty (-1) or holds a lane for which `same(lane)` is true.  Returns
    (bucket, occupant)."""
    def stop(occ):
        return (occ < 0) | same(jnp.maximum(occ, 0))

    def body(carry):
        h, _, _ = carry
        h = (h + 1) & (T - 1)
        occ = tab_ref[h]
        return h, occ, stop(occ)

    occ0 = tab_ref[h0]
    h, occ, _ = lax.while_loop(lambda c: ~c[2], body, (h0, occ0, stop(occ0)))
    return h, occ


def _fused_write_kernel(keys_ref, ops_ref, vals_ref, bounds_ref, index_ref,
                        lk, lv, lp, lm, rk, rv, rp, rm,
                        rep_ref, rep_pos_ref, val_nc_ref, tomb_ref, cold_ref,
                        created_ref, found_ref, addr_ref, inpl_ref, app_ref,
                        new_addr_ref, prevs_ref, slots_ref, pub_ref,
                        heads_ref, rcinv_ref, hops_ref, ios_ref, exh_ref,
                        ktab, stab, last_set, rsum, rcnt, *,
                        B: int, T: int, C: int, R: int, V: int, E: int,
                        chain_max: int):
    cols = (lk, lp, lm, lv, rk, rp, rm, rv)
    begin, hb, ro, tail = (bounds_ref[j] for j in range(4))
    null = jnp.int32(NULL_ADDR)

    def is_write(op):
        return (op == OP_UPSERT) | (op == OP_RMW) | (op == OP_DELETE)

    def clear(i, c):
        ktab[i] = null
        stab[i] = null
        return c

    lax.fori_loop(0, T, clear, 0)

    # pass 1: group by key -> first position; last set per group
    def group(i, c):
        op, key = ops_ref[i], keys_ref[i]
        last_set[i] = null
        rcnt[i] = 0
        for v in range(V):
            rsum[i * V + v] = 0

        @pl.when(is_write(op))
        def _():
            h, occ = _find(ktab, T, _slot(_mix(key), T),
                           lambda j: keys_ref[j] == key)
            r = jnp.where(occ < 0, i, occ)
            ktab[h] = r
            rep_pos_ref[i] = r

            @pl.when((op == OP_UPSERT) | (op == OP_DELETE))
            def _():
                last_set[r] = i

        @pl.when(~is_write(op))
        def _():
            rep_pos_ref[i] = null

        return c

    lax.fori_loop(0, B, group, 0)

    # pass 2: RMW deltas after each group's last set accumulate on the rep
    def accumulate(i, c):
        op, r = ops_ref[i], rep_pos_ref[i]

        @pl.when((op == OP_RMW) & (i > last_set[jnp.maximum(r, 0)]))
        def _():
            rcnt[r] = rcnt[r] + 1
            for v in range(V):
                rsum[r * V + v] = rsum[r * V + v] + vals_ref[i * V + v]

        return c

    lax.fori_loop(0, B, accumulate, 0)

    # pass 3: per-lane locate walk, classification and append plan
    def plan(i, offs):
        op, key = ops_ref[i], keys_ref[i]
        wm = is_write(op)
        r = rep_pos_ref[i]
        rc0 = jnp.maximum(r, 0)
        rep = wm & (r == i)
        ls = jnp.where(wm, last_set[rc0], null)
        has_set = ls >= 0
        lsc = jnp.maximum(ls, 0)
        set_is_del = has_set & (ops_ref[lsc] == OP_DELETE)
        cnt = jnp.where(wm, rcnt[rc0], 0)

        slot = _slot(key, E)
        head = _fetch(index_ref, slot)
        found, faddr, hops, ios, exh, fmeta, fval = _walk(
            key, head, begin, rep, rep & False, hb, cols, C=C, R=R,
            V=V, chain_max=chain_max, rc_match=False, has_rc=True)
        found_tomb = found & ((fmeta & META_TOMBSTONE) != 0)
        pure_rmw = rep & ~has_set & (cnt > 0)
        base_hot = pure_rmw & found & ~found_tomb
        for v in range(V):
            set_val = jnp.where(has_set, vals_ref[lsc * V + v], 0)
            rs = jnp.where(wm, rsum[rc0 * V + v], 0)
            base = jnp.where(base_hot, fval[v], 0)
            val = jnp.where(has_set & ~set_is_del, set_val + rs,
                            jnp.where(has_set & set_is_del & (cnt > 0), rs,
                                      base + rs))
            val_nc_ref[i * V + v] = jnp.where(rep, val, 0)

        in_place = rep & found & (faddr >= ro)
        append = rep & ~in_place
        head_rc = _is_rc(head)
        rc_i = jnp.maximum(head & ~jnp.int32(RC_FLAG), 0) & (R - 1)
        rc_k, rc_p = lax.cond(head_rc,
                              lambda: (_fetch(rk, rc_i), _fetch(rp, rc_i)),
                              lambda: (jnp.int32(0), jnp.int32(0)))
        eff_prev = jnp.where(head_rc, rc_p, head)
        new_addr = jnp.where(append, tail + offs, null)
        slots_ref[i] = slot
        new_addr_ref[i] = new_addr
        pub_ref[i] = _i32(append)
        prevs_ref[i] = jnp.where(append, eff_prev, null)

        # intra-batch chaining: my prev is the previous append in my slot
        @pl.when(append)
        def _():
            h, pred = _find(stab, T, _slot(slot, T),
                            lambda j: slots_ref[j] == slot)
            stab[h] = i

            @pl.when(pred >= 0)
            def _():
                prevs_ref[i] = new_addr_ref[pred]
                pub_ref[pred] = 0

        rep_ref[i] = _i32(rep)
        tomb_ref[i] = _i32(rep & has_set & set_is_del & (cnt == 0))
        cold_ref[i] = _i32(pure_rmw & ~found)
        created_ref[i] = _i32(pure_rmw & ~base_hot)
        found_ref[i] = _i32(found)
        addr_ref[i] = faddr
        inpl_ref[i] = _i32(in_place)
        app_ref[i] = _i32(append)
        heads_ref[i] = head
        rcinv_ref[i] = _i32((append & head_rc)
                            | (in_place & head_rc & (rc_k == key)))
        hops_ref[i] = hops
        ios_ref[i] = ios
        exh_ref[i] = _i32(exh)
        return offs + _i32(append)

    lax.fori_loop(0, B, plan, jnp.int32(0))


def fused_write(keys, ops, vals, index, bounds,
                log_key, log_val, log_prev, log_meta,
                rc_key, rc_val, rc_prev, rc_meta, *,
                chain_max: int, interpret: bool = False,
                site: str = "write.plan"):
    """Fused write-plan pass.  `bounds` packs the four scalars
    (begin, head_boundary, ro_addr, tail) as an int32 [4] array.  The whole
    batch is one call (intra-batch grouping needs every lane).  Returns
    the 19-tuple of `ref.fused_write_body` with `val_nocold` flattened to
    [B*V], every element int32; `site` goes into the kernel metadata as
    in `fused_probe`."""
    B = keys.shape[0]
    C, R = log_key.shape[0], rc_key.shape[0]
    V = log_val.shape[1]
    E = index.shape[0]
    assert (C & (C - 1)) == 0 and (R & (R - 1)) == 0
    T = _table_size(B)
    kernel = functools.partial(_fused_write_kernel, B=B, T=T, C=C, R=R, V=V,
                               E=E, chain_max=chain_max)
    lane = jax.ShapeDtypeStruct((B,), jnp.int32)
    out_shape = ([lane] * 2 + [jax.ShapeDtypeStruct((B * V,), jnp.int32)]
                 + [lane] * 16)
    return pl.pallas_call(
        kernel,
        in_specs=[_SMEM] * 4 + [_VMEM] * 9,
        out_specs=[_SMEM] * 19,
        out_shape=out_shape,
        scratch_shapes=[pltpu.SMEM((T,), jnp.int32),
                        pltpu.SMEM((T,), jnp.int32),
                        pltpu.SMEM((B,), jnp.int32),
                        pltpu.SMEM((B * V,), jnp.int32),
                        pltpu.SMEM((B,), jnp.int32)],
        interpret=interpret,
        metadata={"f2_site": site},
    )(keys, ops, vals.reshape(-1), bounds, as_rows(index),
      *(as_rows(c) for c in (log_key, log_val, log_prev, log_meta,
                             rc_key, rc_val, rc_prev, rc_meta)))
