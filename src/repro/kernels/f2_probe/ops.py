"""jit'd wrappers for the F2 probe/write kernels.

`fused_probe` pads the key batch up to a lane multiple with inactive lanes
(inactive lanes emit found=0, hops=0 and contribute nothing to the modeled
I/O sums) and runs it in chunks of at most `B_TILE` lanes, so callers may
pass any batch size.  `fused_write` pads to a lane multiple with OP_NOOP
lanes, which never group, walk, append, or publish.

`interpret=None` runs the kernels compiled on a TPU and in interpret mode
on any other backend; asking for interpret mode on a TPU is an error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .f2_probe import LANES
from .f2_probe import fused_probe as _fused_kernel
from .f2_probe import fused_write as _fused_write_kernel
from .f2_probe import probe as _kernel
from .ref import fused_probe_reference, fused_write_reference, probe_reference


B_TILE = 2048      # lanes per fused_probe kernel call


def _interpret(interpret: bool | None) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("interpret mode requested on a TPU backend")
    return interpret


@functools.partial(jax.jit, static_argnames=("interpret",))
def probe(keys, index_addr, *, interpret: bool | None = None):
    return _kernel(keys, index_addr, interpret=_interpret(interpret))


def _mapped_in_a_loop(fn, *args):
    """Call `fn(*args)`; under `jax.vmap` (the sharded store lifts every
    store step over its shard axis) each batch element becomes its own
    kernel call.  A batched pallas_call would put a leading batch axis on
    the SMEM blocks, which the TPU lowering rejects."""
    return jax.custom_batching.sequential_vmap(fn)(*args)


def _pad_to(x, n, fill=0):
    pad = n - x.shape[0]
    if not pad:
        return x
    widths = ((0, pad),) + ((0, 0),) * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def fused_probe(keys, heads_src, lower, active, head_boundary,
                log_key, log_val, log_prev, log_meta,
                rc_key, rc_val, rc_prev, rc_meta, *,
                chain_max: int, rc_match: bool = True, has_rc: bool = True,
                probe_index: bool = True, target=None,
                interpret: bool | None = None, site: str = "probe"):
    """Callable under an outer jit.  Boolean masks in/out; pads B to a lane
    multiple and runs chunks of at most `B_TILE` lanes.  Returns (found,
    addr, heads, value, meta, hops, ios, exhausted) exactly like
    `ref.fused_probe_reference`.  `site` (a `probe_engine.SITES` name)
    goes into each kernel call's metadata."""
    itp = _interpret(interpret)
    B = keys.shape[0]
    V = log_val.shape[1]
    Bp = -(-B // LANES) * LANES
    lanes = dict(keys=_pad_to(keys, Bp), lower=_pad_to(lower, Bp),
                 active=_pad_to(active.astype(jnp.int32), Bp))
    if not probe_index:
        lanes["heads"] = _pad_to(heads_src, Bp, fill=-1)
    if target is not None:
        lanes["target"] = _pad_to(target, Bp, fill=-1)
    hb = jnp.reshape(head_boundary.astype(jnp.int32), (1,))

    def call(keys, heads, lower, active, hb, *rest):
        target, cols = (rest[0], rest[1:]) if "target" in lanes else (None,
                                                                       rest)
        return tuple(_fused_kernel(
            keys, heads, lower, active, hb, *cols, chain_max=chain_max,
            rc_match=rc_match, has_rc=has_rc, probe_index=probe_index,
            target=target, interpret=itp, site=site))

    outs = []
    for s in range(0, Bp, B_TILE):
        sl = {k: v[s:s + B_TILE] for k, v in lanes.items()}
        outs.append(_mapped_in_a_loop(
            call, sl["keys"], heads_src if probe_index else sl["heads"],
            sl["lower"], sl["active"], hb,
            *([sl["target"]] if "target" in sl else []),
            log_key, log_val, log_prev, log_meta,
            rc_key, rc_val, rc_prev, rc_meta))
    found, addr, heads, value, meta, hops, ios, exhausted = (
        jnp.concatenate(xs)[:B * V if i == 3 else B]
        for i, xs in enumerate(zip(*outs)))
    return (found != 0, addr, heads, value.reshape(B, V), meta, hops, ios,
            exhausted != 0)


def fused_write(keys, ops, vals, index, begin, head_boundary, ro_addr, tail,
                log_key, log_val, log_prev, log_meta,
                rc_key, rc_val, rc_prev, rc_meta, *,
                chain_max: int, interpret: bool | None = None,
                site: str = "write.plan"):
    """Callable under an outer jit.  Pads B up to a lane multiple with
    OP_NOOP lanes (inert: no grouping, no walk, no append).  Boolean masks
    out; returns the 19-tuple of `ref.fused_write_body`.  `site` goes
    into the kernel call's metadata."""
    itp = _interpret(interpret)
    B = keys.shape[0]
    V = vals.shape[1]
    Bp = -(-B // LANES) * LANES
    bounds = jnp.stack([jnp.int32(begin), jnp.int32(head_boundary),
                        jnp.int32(ro_addr), jnp.int32(tail)])

    def call(*args):
        return tuple(_fused_write_kernel(*args, chain_max=chain_max,
                                         interpret=itp, site=site))

    out = _mapped_in_a_loop(
        call, _pad_to(keys, Bp), _pad_to(ops, Bp),  # 0 == OP_NOOP: inert
        _pad_to(vals, Bp), index, bounds,
        log_key, log_val, log_prev, log_meta,
        rc_key, rc_val, rc_prev, rc_meta)
    (rep, rep_pos, val_nocold, final_tomb, need_cold, created_nocold,
     found, addr, in_place, append, new_addrs, prevs, slots, publish,
     heads, rc_inval, hops, ios, exhausted) = (
        x[:B * V] if i == 2 else x[:B] for i, x in enumerate(out))
    return (rep != 0, rep_pos, val_nocold.reshape(B, V), final_tomb != 0,
            need_cold != 0, created_nocold != 0, found != 0, addr,
            in_place != 0, append != 0, new_addrs, prevs, slots,
            publish != 0, heads, rc_inval != 0, hops, ios, exhausted != 0)


probe_ref = probe_reference
fused_probe_ref = fused_probe_reference
fused_write_ref = fused_write_reference
