"""Write engine dispatch: the kernel-backend pattern for the mutate path.

`store.write_batch` is, per batch: per-key linearization (last-set
selection + RMW accumulation), a hot-log locate walk that skips read-cache
replicas, in-place-vs-RCU classification against the mutable boundary,
intra-batch chain-offset computation, and append-address/index-publish
preparation.  This module fuses all of that into one engine pass with the
same three interchangeable, bit-exact backends as `probe_engine`, selected
by the same `F2Config.engine` knob:

    "jnp"           — the unfused path: `groups` argsort linearization +
                      `chain.walk` + separate gathers (the seed
                      implementation, kept as the oracle).
    "fused_ref"     — pure-jnp single-pass reference of the fused engine
                      (B x B group masks instead of argsort).
    "fused_pallas"  — the Pallas kernel (`kernels.f2_probe.fused_write`,
                      O(B) hash-table grouping); interpret mode off-TPU.
    "fused"         — auto (default): the Pallas kernel on a TPU when the
                      log/RC/index columns fit its VMEM budget and the
                      batch's lane arrays and grouping tables fit its SMEM
                      budget, the fused reference otherwise (reported per
                      call site, `probe_engine.engine_choices(cfg)`).

The engine emits a `WritePlan` — everything `store.write_batch` needs to
mutate state with plain scatters — rather than mutating state itself, so
log/RC/index updates stay in one place and the cold-log base lookup for
pure-RMW groups (the only part that needs the cold index) composes outside
the pass.  All backends return the same `WritePlan` bit-exactly; the parity
suite (tests/test_write_engine.py) enforces this.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.f2_probe import f2_probe as _kernel_mod
from ..kernels.f2_probe import ops as probe_ops
from ..kernels.f2_probe import ref as _ref_mod
from ..kernels.f2_probe.ref import fused_write_reference
from . import chain, groups, hybrid_log, probe_engine, read_cache
from .types import (META_TOMBSTONE, NULL_ADDR, OP_DELETE, OP_RMW, OP_UPSERT,
                    F2Config, hash32, is_rc, rc_untag)

# the kernel package re-declares op codes and meta bits (import-standalone
# by design; the Pallas kernel imports them from ref, so ref is the one
# place drift could enter); fail loudly, like probe_engine does for
# addresses
assert _ref_mod.OP_UPSERT == OP_UPSERT
assert _ref_mod.OP_RMW == OP_RMW
assert _ref_mod.OP_DELETE == OP_DELETE
assert _ref_mod.META_TOMBSTONE == int(META_TOMBSTONE)

_BIG = np.int32(2**30)


class WritePlan(NamedTuple):
    """Everything write_batch needs to apply a mutate batch.

    Per-lane fields are fully masked (deterministic for every lane), so
    backends can be compared bit-exactly.  `val_nocold` / `created_nocold`
    are the final value / RMW-created verdict assuming the cold log
    contributes nothing; `need_cold` marks the pure-RMW lanes whose base
    value must still be resolved from the cold tier.
    """
    rep: jax.Array             # bool  [B] one mutating lane per key group
    rep_pos: jax.Array         # int32 [B] batch position of my group's rep (-1)
    val_nocold: jax.Array      # int32 [B, V] final value sans cold base
    final_tomb: jax.Array      # bool  [B] rep writes a tombstone
    need_cold: jax.Array       # bool  [B] pure-RMW miss: resolve cold base
    created_nocold: jax.Array  # bool  [B] RMW creates unless cold supplies base
    found: jax.Array           # bool  [B] locate walk found a live log record
    addr: jax.Array            # int32 [B] its address (NULL when not found)
    in_place: jax.Array        # bool  [B] mutable-region in-place update
    append: jax.Array          # bool  [B] RCU append at the tail
    new_addrs: jax.Array       # int32 [B] assigned append addresses (NULL)
    prevs: jax.Array           # int32 [B] chain prev per append (intra-batch)
    slots: jax.Array           # int32 [B] hot-index slot per lane
    publish: jax.Array         # bool  [B] last append of its slot run
    heads: jax.Array           # int32 [B] resolved index heads (may be RC)
    rc_inval: jax.Array        # bool  [B] invalidate the RC head replica
    hops: jax.Array            # int32 [B] per-lane walk record touches
    io_blocks: jax.Array       # int32 scalar: stable-tier blocks read
    io_ops: jax.Array          # int32 scalar: random read ops issued
    mem_hits: jax.Array        # int32 scalar: in-memory record touches
    exhausted: jax.Array       # bool  [B] chain_max hops without resolution


def _resolve(cfg: F2Config, engine: Optional[str], site: str,
             log: hybrid_log.LogState, rc: read_cache.RCState,
             B: int) -> str:
    V = log.val.shape[1]
    vmem = _kernel_mod.probe_vmem_bytes(log.key.shape[0], rc.key.shape[0],
                                        V, cfg.hot_index_size)
    smem = _kernel_mod.write_smem_bytes(B, V)
    return probe_engine.choose(
        site, cfg.engine if engine is None else engine, vmem, smem).backend


def plan(
    cfg: F2Config,
    keys: jax.Array,            # int32 [B]
    ops: jax.Array,             # int32 [B]
    vals: jax.Array,            # int32 [B, V]
    log: hybrid_log.LogState,   # the hot log
    index: jax.Array,           # int32 [E] hot-index chain heads
    rc: read_cache.RCState,
    *,
    engine: Optional[str] = None,
    site: str = "write.plan",           # call-site name (`probe_engine.SITES`)
) -> WritePlan:
    """One fused write-plan pass over a mutate batch (backend per
    cfg.engine)."""
    engine = _resolve(cfg, engine, site, log, rc, keys.shape[0])
    assert engine in ("jnp", "fused_ref", "fused_pallas"), engine
    if engine == "jnp":
        return _plan_unfused(cfg, keys, ops, vals, log, index, rc)

    hb = hybrid_log.head_addr(log, cfg.hot_mem)
    ro = hybrid_log.read_only_addr(log, cfg.hot_mem, cfg.hot_mutable_frac)
    args = (keys, ops, vals, index)
    cols = (log.key, log.val, log.prev, log.meta,
            rc.key, rc.val, rc.prev, rc.meta)
    if engine == "fused_pallas":
        out = probe_ops.fused_write(*args, log.begin, hb, ro, log.tail,
                                    *cols, chain_max=cfg.chain_max,
                                    site=site)
    else:
        # the reference early-exits once every lane resolved (bit-exact)
        with jax.named_scope(site):
            out = fused_write_reference(*args, log.begin, hb, ro, log.tail,
                                        *cols, chain_max=cfg.chain_max,
                                        early_exit=True)
    (rep, rep_pos, val_nocold, final_tomb, need_cold, created_nocold,
     found, addr, in_place, append, new_addrs, prevs, slots, publish,
     heads, rc_inval, hops, ios, exhausted) = out
    n_io = jnp.sum(ios)
    return WritePlan(rep=rep, rep_pos=rep_pos, val_nocold=val_nocold,
                     final_tomb=final_tomb, need_cold=need_cold,
                     created_nocold=created_nocold, found=found, addr=addr,
                     in_place=in_place, append=append, new_addrs=new_addrs,
                     prevs=prevs, slots=slots, publish=publish, heads=heads,
                     rc_inval=rc_inval, hops=hops, io_blocks=n_io,
                     io_ops=n_io, mem_hits=jnp.sum(hops) - n_io,
                     exhausted=exhausted)


def _plan_unfused(cfg, keys, ops, vals, log, index, rc) -> WritePlan:
    """The seed write path's computation, repackaged as a plan: argsort
    linearization + `chain.walk` + separate gathers.  Kept bit-exact as the
    oracle the fused backends are tested against."""
    B = keys.shape[0]
    wmask = (ops == OP_UPSERT) | (ops == OP_RMW) | (ops == OP_DELETE)
    is_set = (ops == OP_UPSERT) | (ops == OP_DELETE)
    pos = jnp.arange(B, dtype=jnp.int32)

    # --- per-key linearization (group by key) -------------------------------
    info, last_set_pos = groups.segment_reduce_last_set(wmask, keys, is_set, B)
    has_set = last_set_pos >= 0
    set_val = groups.select_at_pos(vals, pos, last_set_pos)
    set_op = groups.select_at_pos(ops, pos, last_set_pos)
    set_is_del = has_set & (set_op == OP_DELETE)
    rmw_after = wmask & (ops == OP_RMW) & (pos > last_set_pos)
    rmw_sum = groups.segment_sum_where(vals, rmw_after, info.run_id, B)
    rmw_cnt = groups.segment_sum_where(rmw_after.astype(jnp.int32),
                                       rmw_after, info.run_id, B)
    rep = wmask & info.is_first
    seg = jnp.where(info.run_id >= 0, info.run_id, B - 1)
    first_pos = jax.ops.segment_min(jnp.where(wmask, pos, _BIG), seg,
                                    num_segments=B)
    rep_pos = jnp.where(wmask, first_pos[seg], -1)

    # --- locate the most recent *log* record (skip RC replicas) -------------
    slots = (hash32(keys) & jnp.uint32(cfg.hot_index_size - 1)).astype(jnp.int32)
    heads = index[slots]
    hot_head = hybrid_log.head_addr(log, cfg.hot_mem)
    ro_addr = hybrid_log.read_only_addr(log, cfg.hot_mem, cfg.hot_mutable_frac)
    lower = jnp.broadcast_to(log.begin, (B,))
    res = chain.walk(keys, heads, log, lower, hot_head, rep, cfg.chain_max,
                     rc=rc, rc_match=False)
    found = res.found
    _, fval, _, fmeta = hybrid_log.gather(log, jnp.where(found, res.addr, 0))
    found_tomb = found & ((fmeta & META_TOMBSTONE) != 0)
    found_mut = found & (res.addr >= ro_addr)

    # --- base value for pure-RMW groups -------------------------------------
    pure_rmw = rep & ~has_set & (rmw_cnt > 0)
    base_hot = pure_rmw & found & ~found_tomb
    need_cold = pure_rmw & ~found        # hot tombstone => absent, skip cold
    created_nocold = pure_rmw & ~base_hot

    base = jnp.where(base_hot[:, None], fval, 0)
    val_nocold = jnp.where(has_set[:, None] & ~set_is_del[:, None],
                           set_val + rmw_sum,
                           jnp.where((has_set & set_is_del
                                      & (rmw_cnt > 0))[:, None],
                                     rmw_sum, base + rmw_sum))
    val_nocold = jnp.where(rep[:, None], val_nocold, 0)
    final_tomb = rep & has_set & set_is_del & (rmw_cnt == 0)

    # --- in-place (mutable region) vs RCU append ----------------------------
    in_place = rep & found_mut
    append = rep & ~in_place

    head_is_rc = is_rc(heads)
    rc_k, _, rc_p, _ = read_cache.gather(rc, rc_untag(heads))
    eff_prev = jnp.where(head_is_rc, rc_p, heads)
    rc_inval = (append & head_is_rc) | (in_place & head_is_rc
                                        & (rc_k == keys))

    # --- intra-batch chaining by hash slot ----------------------------------
    ginfo = groups.group_info(append, slots)
    a32 = append.astype(jnp.int32)
    offs = jnp.cumsum(a32) - a32
    new_addrs = jnp.where(append, log.tail + offs, NULL_ADDR)
    pred_addr = groups.select_at_pos(new_addrs, pos, ginfo.pred)
    prevs = jnp.where(append,
                      jnp.where(ginfo.pred >= 0, pred_addr, eff_prev),
                      NULL_ADDR)
    publish = append & ginfo.is_last

    return WritePlan(rep=rep, rep_pos=rep_pos, val_nocold=val_nocold,
                     final_tomb=final_tomb, need_cold=need_cold,
                     created_nocold=created_nocold, found=found,
                     addr=res.addr, in_place=in_place, append=append,
                     new_addrs=new_addrs, prevs=prevs, slots=slots,
                     publish=publish, heads=heads, rc_inval=rc_inval,
                     hops=res.hops, io_blocks=res.io_blocks,
                     io_ops=res.io_ops, mem_hits=res.mem_hits,
                     exhausted=res.exhausted)
