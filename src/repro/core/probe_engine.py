"""Probe engine dispatch: the kernel-backend pattern for the read hot path.

Every bounded chain walk in the store (hot-index probe on reads, the
liveness probe of ConditionalInsert, the cold-chain walk) is the same
primitive: slot hash / chain head -> bounded prev-pointer walk with a
per-lane address lower bound -> read-cache hit check -> value resolution.
This module gives that primitive one interface with three interchangeable,
bit-exact backends, selected by `F2Config.engine`:

    "jnp"           — the unfused path: `chain.walk` + separate gathers
                      (the seed implementation, kept as the oracle).
    "fused_ref"     — pure-jnp single-pass reference of the fused engine.
    "fused_pallas"  — the Pallas kernel (`kernels.f2_probe.fused_probe`);
                      interpret mode off-TPU.
    "fused"         — auto (default): the Pallas kernel on a TPU when the
                      log/RC/index columns fit the VMEM budget the v5e
                      compiler accepts, the fused reference otherwise.
                      Each call site's resolution and its reason follow
                      from the config (`engine_choices(cfg)`, reported
                      under the facades' `stats()["engines"]`).

All backends return the same `ProbeResult` bit-exactly, so store-level
behaviour (statuses, values, modeled I/O) is engine-independent; the parity
suite (tests/test_probe_engine.py) enforces this.  The `target=` mode adds
lookup-based compaction's zero-I/O liveness fast path (`head == addr`
resolves before the first hop) as an in-engine predicate — all three
compaction steps probe through it.  The mutate pipeline has its own engine
built on the same pattern (`core.write_engine`).  Later subsystems that
want a kernel backend (cold-index chunk probe, compaction frontier scan)
should follow this module's shape: one result type, one dispatch knob, a
jnp oracle that stays in the tree.
"""
from __future__ import annotations

from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..kernels.f2_probe import f2_probe as _kernel_mod
from ..kernels.f2_probe import ops as probe_ops
from ..kernels.f2_probe import ref as _ref_mod
from ..kernels.f2_probe.ref import fused_probe_reference
from . import chain, hybrid_log, read_cache
from .types import (META_INVALID, NULL_ADDR, RC_FLAG, F2Config, hash32,
                    is_rc, rc_untag)

ENGINES = ("jnp", "fused", "fused_ref", "fused_pallas")

# kernel packages are import-standalone by design (no repro.core dependency),
# so the address/meta bit layout is re-declared there; this module imports
# both sides and is where drift would break things — fail loudly instead
# (the slot hash's agreement is checked in tests/test_probe_engine.py)
for _m in (_kernel_mod, _ref_mod):
    assert _m.RC_FLAG == int(RC_FLAG), _m
    assert _m.NULL_ADDR == int(NULL_ADDR), _m
    assert _m.META_INVALID == int(META_INVALID), _m


class EngineChoice(NamedTuple):
    backend: str    # "fused_pallas" | "fused_ref" | "jnp" | "host_tier"
    reason: str


def _mib(n: int) -> str:
    return f"{n / 2**20:.1f} MiB"


def choose(site: str, engine: str, vmem: int, smem: int = 0) -> EngineChoice:
    """Resolve `engine` for one call site.  "fused" takes the Pallas
    kernel only on a TPU and only when its VMEM-resident columns (`vmem`
    bytes) and per-lane SMEM arrays (`smem` bytes) fit the budgets the v5e
    compiler accepts; everything else runs the fused jnp reference.  A
    forced "fused_pallas" that cannot fit raises."""
    on_tpu = jax.default_backend() == "tpu"
    over = []
    if vmem > _kernel_mod.VMEM_BUDGET_BYTES:
        over.append(f"columns {_mib(vmem)} exceed the VMEM budget "
                    f"{_mib(_kernel_mod.VMEM_BUDGET_BYTES)}")
    if smem > _kernel_mod.SMEM_BUDGET_BYTES:
        over.append(f"batch needs {_mib(smem)} of SMEM, over the budget "
                    f"{_mib(_kernel_mod.SMEM_BUDGET_BYTES)}")
    if engine != "fused":
        if engine == "fused_pallas" and on_tpu and over:
            raise ValueError(f"{site}: engine='fused_pallas' forced but "
                             + "; ".join(over))
        return EngineChoice(engine, f"forced by engine={engine!r}")
    if not on_tpu:
        return EngineChoice("fused_ref", (
            f"backend {jax.default_backend()}: the kernel runs compiled "
            "only on a TPU"))
    if over:
        return EngineChoice("fused_ref", "; ".join(over))
    return EngineChoice("fused_pallas", (
        f"fits: columns {_mib(vmem)} of "
        f"{_mib(_kernel_mod.VMEM_BUDGET_BYTES)} VMEM"))


class Site(NamedTuple):
    """The columns one engine call site hands the kernel."""
    log: str             # "hot" | "cold": whose log columns
    rc: bool             # the read-cache columns too
    index: bool          # the hot index too (fused slot lookup)
    host: bool = False   # a cold walk that is host-tier aware (`_cold_probe`)
    write: bool = False  # the write kernel: SMEM grows with the batch


SITES: Dict[str, Site] = {
    "read.hot": Site("hot", True, True),
    "read.cold": Site("cold", False, False, host=True),
    "probe_hops.hot": Site("hot", True, True),
    "probe_hops.cold": Site("cold", False, False, host=True),
    "read_finish.hot": Site("hot", True, False),
    "read_finish.cold": Site("cold", False, False, host=True),
    "read_finish.retry": Site("cold", False, False, host=True),
    "plan_fetch.hot": Site("hot", True, True),
    "plan_finish.hot": Site("hot", True, False),
    "write.plan": Site("hot", True, True, write=True),
    "write.cold_base": Site("cold", False, False, host=True),
    "conditional_insert": Site("hot", True, True),
    "compact.hot_cold": Site("hot", True, True),
    "compact.cold_cold": Site("cold", False, False, host=True),
    "compact.single_log": Site("hot", True, True),
    "drain.hot": Site("hot", True, True),
    "drain.cold": Site("cold", False, False),
}


def site_bytes(cfg: F2Config, site: str, lanes: int) -> tuple:
    """(VMEM, SMEM) bytes the kernel of `site` needs under `cfg` for a
    batch of `lanes` — the same numbers `probe` / `write_engine.plan`
    compute from the array shapes at trace time."""
    s = SITES[site]
    V = cfg.value_width
    C = cfg.hot_capacity if s.log == "hot" else cfg.cold_capacity
    R = max(cfg.rc_capacity, 1) if s.rc else 1
    vmem = _kernel_mod.probe_vmem_bytes(
        C, R, V, cfg.hot_index_size if s.index else 0)
    if s.write:
        return vmem, _kernel_mod.write_smem_bytes(lanes, V)
    return vmem, _kernel_mod.probe_smem_bytes(probe_ops.B_TILE, V)


def engine_choices(cfg: F2Config, write_lanes: int = 0
                   ) -> Dict[str, Dict[str, str]]:
    """{call site: {"backend", "reason"}}: what `cfg.engine` resolves to
    on this backend at every engine call site, for a store built from
    `cfg`.  `write_lanes` is the mutate batch width the write kernel sees
    (the facades pass the widest they have run); "write.plan" is left out
    while it is 0."""
    out = {}
    for site, s in SITES.items():
        if s.write and write_lanes <= 0:
            continue
        if s.host and cfg.host_tier:
            c = EngineChoice("host_tier", "host tier on: floor-aware jnp walk")
        else:
            c = choose(site, cfg.engine, *site_bytes(cfg, site, write_lanes))
        out[site] = c._asdict()
    return out


class ProbeResult(NamedTuple):
    found: jax.Array      # bool  [B] matching, valid record found
    addr: jax.Array       # int32 [B] its address (RC-tagged for replicas)
    heads: jax.Array      # int32 [B] resolved chain heads (index entries)
    value: jax.Array      # int32 [B, V] record value (0 when not found)
    meta: jax.Array       # int32 [B] record meta bitfield (0 when not found)
    hops: jax.Array       # int32 [B] per-lane record touches
    io_blocks: jax.Array  # int32 scalar: stable-tier blocks read
    io_ops: jax.Array     # int32 scalar: random read ops issued
    mem_hits: jax.Array   # int32 scalar: in-memory record touches
    exhausted: jax.Array  # bool  [B] chain_max hops without resolution


def _resolve(site: str, engine: str, log: hybrid_log.LogState,
             rc: Optional[read_cache.RCState], n_index: int) -> str:
    """n_index: hot-index entries the kernel holds in VMEM (0 when the
    chain heads come per lane).  SMEM is charged for a full `B_TILE`
    chunk, whatever the batch."""
    V = log.val.shape[1]
    R = rc.key.shape[0] if rc is not None else 1
    vmem = _kernel_mod.probe_vmem_bytes(log.key.shape[0], R, V, n_index)
    smem = _kernel_mod.probe_smem_bytes(probe_ops.B_TILE, V)
    return choose(site, engine, vmem, smem).backend


def probe(
    cfg: F2Config,
    keys: jax.Array,            # int32 [B]
    log: hybrid_log.LogState,
    lower: jax.Array,           # int32 [B] per-lane lower bound
    head_boundary: jax.Array,   # int32 scalar (I/O model boundary)
    active: jax.Array,          # bool [B]
    *,
    index: Optional[jax.Array] = None,   # int32 [E]: fuse the slot probe
    heads: Optional[jax.Array] = None,   # int32 [B]: precomputed chain heads
    rc: Optional[read_cache.RCState] = None,
    rc_match: bool = True,
    target: Optional[jax.Array] = None,   # int32 [B]: liveness fast path
    engine: Optional[str] = None,
    site: str = "probe",                  # call-site name (`SITES`)
) -> ProbeResult:
    """One fused probe pass.  Exactly one of `index` / `heads` is given:
    `index` fuses the hot-index slot hash + gather into the pass (read path,
    ConditionalInsert); `heads` starts from externally resolved entries
    (cold-index chains).

    `target` is the liveness mode of lookup-based compaction: a lane whose
    resolved chain head equals its target address is found at the target
    with zero hops and zero modeled I/O (the paper's `head == addr` pure
    address compare), and only the remaining lanes walk.  Callers test
    `found & (addr == target)` for the liveness verdict."""
    assert (index is None) != (heads is None)
    engine = _resolve(site, cfg.engine if engine is None else engine, log,
                      rc, 0 if index is None else index.shape[0])
    assert engine in ("jnp", "fused_ref", "fused_pallas"), engine

    if engine == "jnp":
        return _probe_unfused(cfg, keys, log, lower, head_boundary, active,
                              index=index, heads=heads, rc=rc,
                              rc_match=rc_match, target=target)

    has_rc = rc is not None
    # the kernel signature is total — absent RC becomes 1-record dummies
    # (never dereferenced: without RC no address carries the RC tag)
    if has_rc:
        rck, rcv, rcp, rcm = rc.key, rc.val, rc.prev, rc.meta
    else:
        rck = jnp.full((1,), -1, jnp.int32)
        rcv = jnp.zeros((1, log.val.shape[1]), jnp.int32)
        rcp = jnp.full((1,), NULL_ADDR, jnp.int32)
        rcm = jnp.zeros((1,), jnp.int32)
    probe_index = index is not None
    heads_src = index if probe_index else heads
    args = (keys, heads_src, lower, active, head_boundary,
            log.key, log.val, log.prev, log.meta, rck, rcv, rcp, rcm)
    kw = dict(chain_max=cfg.chain_max, rc_match=rc_match, has_rc=has_rc,
              probe_index=probe_index, target=target)
    if engine == "fused_pallas":
        out = probe_ops.fused_probe(*args, site=site, **kw)
    else:
        # the reference early-exits once every lane resolved (bit-exact)
        with jax.named_scope(site):
            out = fused_probe_reference(*args, early_exit=True, **kw)
    found, addr, heads_out, value, meta, hops, ios, exhausted = out
    n_io = jnp.sum(ios)
    return ProbeResult(found=found, addr=addr, heads=heads_out, value=value,
                       meta=meta, hops=hops, io_blocks=n_io, io_ops=n_io,
                       mem_hits=jnp.sum(hops) - n_io, exhausted=exhausted)


def _probe_unfused(cfg, keys, log, lower, head_boundary, active, *,
                   index, heads, rc, rc_match, target=None) -> ProbeResult:
    """The seed read path, repackaged: walk then gather.  Kept bit-exact as
    the oracle the fused backends are tested against.  (With RC admission
    on, read_batch re-gathers the RC for p_rc — one redundant gather on
    this debugging path; accepted rather than widening every backend's
    interface with a `prev` output.)  The `target` fast path pre-filters
    the walk exactly like the seed compaction steps did: fast lanes never
    enter the walk, so they charge no hops and no I/O."""
    if heads is None:
        slots = (hash32(keys) & jnp.uint32(index.shape[0] - 1)).astype(jnp.int32)
        heads = index[slots]
    if target is not None:
        fast = active & (heads == target)
        walk_active = active & ~fast
    else:
        fast = jnp.zeros_like(active)
        walk_active = active
    res = chain.walk(keys, heads, log, lower, head_boundary, walk_active,
                     cfg.chain_max, rc=rc, rc_match=rc_match)
    found = res.found | fast
    addr = jnp.where(fast, heads, res.addr)
    hit_rc = found & is_rc(addr)
    hit_log = found & ~hit_rc
    _, v_log, _, m_log = hybrid_log.gather(log, jnp.where(hit_log, addr, 0))
    value = jnp.where(hit_log[:, None], v_log, 0)
    meta = jnp.where(hit_log, m_log, 0)
    if rc is not None:
        _, v_rc, _, m_rc = read_cache.gather(rc, rc_untag(addr))
        value = jnp.where(hit_rc[:, None], v_rc, value)
        meta = jnp.where(hit_rc, m_rc, meta)
    return ProbeResult(found=found, addr=addr, heads=heads,
                       value=value, meta=meta, hops=res.hops,
                       io_blocks=res.io_blocks, io_ops=res.io_ops,
                       mem_hits=res.mem_hits, exhausted=res.exhausted)
