"""Compile-only guards: the F2 Pallas kernels compile for a TPU v5e.

Interpret mode has no VMEM or SMEM limit and accepts gathers Mosaic
cannot lower, so the parity suites cannot see whether the kernels build
for the chip.  These tests compile them for a described (not attached)
v5e at the largest shapes the engines' budgets admit, at an odd padded
batch, and under `vmap` (how the sharded store calls them), and check
that the engine refuses the kernel past its budgets.  The topology is
described inside a fixture, so importing this file touches no TPU
library.
"""
import dataclasses
import types

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import F2Config, probe_engine, write_engine
from repro.kernels.f2_probe import f2_probe as K
from repro.kernels.f2_probe import ops as kops

V = 2
CHAIN_MAX = 48


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _largest_admitted():
    """(C, R, E): power-of-two log/read-cache/index sizes that fit the
    VMEM budget, where doubling any one of them would not."""
    C, R, E = 1 << 21, 1 << 19, 1 << 21
    fits = lambda c, r, e: K.probe_vmem_bytes(c, r, V, e) \
        <= K.VMEM_BUDGET_BYTES                                  # noqa: E731
    assert fits(C, R, E)
    assert not (fits(2 * C, R, E) or fits(C, 2 * R, E) or fits(C, R, 2 * E))
    return C, R, E


def _largest_write_batch():
    B = K.LANES
    while K.write_smem_bytes(B + K.LANES, V) <= K.SMEM_BUDGET_BYTES:
        B += K.LANES
    return B


def _cols(sds, C, R):
    return [sds((C,)), sds((C, V)), sds((C,)), sds((C,)),
            sds((R,)), sds((R, V)), sds((R,)), sds((R,))]


def _probe_fn(probe_index=True, has_target=False):
    def f(keys, heads, lower, active, hb, *rest):
        target, cols = (rest[0], rest[1:]) if has_target else (None, rest)
        return kops.fused_probe(keys, heads, lower, active, hb, *cols,
                                chain_max=CHAIN_MAX, probe_index=probe_index,
                                target=target, interpret=False)
    return f


def _write_fn(keys, ops, vals, index, b0, b1, b2, b3, *cols):
    return kops.fused_write(keys, ops, vals, index, b0, b1, b2, b3, *cols,
                            chain_max=CHAIN_MAX, interpret=False)


def _compile(fn, args, batch=None):
    if batch is not None:
        fn = jax.vmap(fn)
        args = [jax.ShapeDtypeStruct((batch,) + a.shape, a.dtype,
                                     sharding=a.sharding) for a in args]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo


def _sds(one_chip):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return sds


@pytest.mark.parametrize("mode", ["index", "heads_target"])
def test_probe_compiles_at_budget(one_chip, no_persistent_cache, mode):
    sds = _sds(one_chip)
    C, R, E = _largest_admitted()
    B = kops.B_TILE
    if mode == "index":
        fn, heads, target = _probe_fn(), sds((E,)), []
    else:
        fn = _probe_fn(probe_index=False, has_target=True)
        heads, target = sds((B,)), [sds((B,))]
    _compile(fn, [sds((B,)), heads, sds((B,)), sds((B,), jnp.bool_),
                  sds(())] + target + _cols(sds, C, R))


def test_write_compiles_at_budget(one_chip, no_persistent_cache):
    sds = _sds(one_chip)
    C, R, E = _largest_admitted()
    B = _largest_write_batch()
    _compile(_write_fn, [sds((B,)), sds((B,)), sds((B, V)), sds((E,))]
             + [sds(())] * 4 + _cols(sds, C, R))


@pytest.mark.parametrize("batch", [None, 4], ids=["plain", "vmapped"])
def test_kernels_compile_at_odd_batch(one_chip, no_persistent_cache, batch):
    """B=77 pads to a lane multiple; `vmap` is how ShardedKV lifts them."""
    sds = _sds(one_chip)
    B, C, R, E = 77, 1 << 12, 1 << 10, 1 << 12
    _compile(_probe_fn(), [sds((B,)), sds((E,)), sds((B,)),
                           sds((B,), jnp.bool_), sds(())]
             + _cols(sds, C, R), batch)
    _compile(_write_fn, [sds((B,)), sds((B,)), sds((B, V)), sds((E,))]
             + [sds(())] * 4 + _cols(sds, C, R), batch)


@pytest.mark.parametrize("kernel,site", [("probe", "read.cold"),
                                         ("write", "write.plan")])
def test_kernel_carries_its_site(one_chip, no_persistent_cache, kernel,
                                 site):
    """Each kernel call names its engine site (`probe_engine.SITES`) in
    its metadata, so the custom call a profile records says which site
    ran it."""
    sds = _sds(one_chip)
    B, C, R, E = 128, 1 << 12, 1 << 10, 1 << 12
    if kernel == "probe":
        fn = lambda *a: kops.fused_probe(                   # noqa: E731
            *a, chain_max=CHAIN_MAX, interpret=False, site=site)
        args = [sds((B,)), sds((E,)), sds((B,)), sds((B,), jnp.bool_),
                sds(())] + _cols(sds, C, R)
    else:
        fn = lambda *a: kops.fused_write(                   # noqa: E731
            *a, chain_max=CHAIN_MAX, interpret=False, site=site)
        args = [sds((B,)), sds((B,)), sds((B, V)), sds((E,))] \
            + [sds(())] * 4 + _cols(sds, C, R)
    lowered = jax.jit(fn).lower(*args)
    text = lowered.as_text()
    assert "kernel_metadata" in text and f"f2_site\\22:\\22{site}" in text
    hlo = lowered.compile().as_text()
    assert "tpu_custom_call" in hlo and f'"f2_site":"{site}"' in hlo


def test_resolve_refuses_kernel_past_budget(monkeypatch):
    """On a TPU, "fused" takes the kernel only inside both budgets and
    says why; a forced kernel past a budget raises.  Two stores of
    different sizes each report their own choice."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def log(C):
        return types.SimpleNamespace(
            key=jax.ShapeDtypeStruct((C,), jnp.int32),
            val=jax.ShapeDtypeStruct((C, V), jnp.int32))

    C, R, E = _largest_admitted()
    rc = log(R)
    assert probe_engine._resolve("read.hot", "fused", log(C), rc,
                                 E) == "fused_pallas"
    assert probe_engine._resolve("read.hot", "fused", log(2 * C), rc,
                                 E) == "fused_ref"

    cfg = types.SimpleNamespace(engine="fused", hot_index_size=E)
    B = _largest_write_batch()
    assert write_engine._resolve(cfg, None, "write.plan", log(C), rc,
                                 B) == "fused_pallas"
    assert write_engine._resolve(cfg, None, "write.plan", log(C), rc,
                                 B + K.LANES) == "fused_ref"
    with pytest.raises(ValueError, match="VMEM budget"):
        write_engine._resolve(cfg, "fused_pallas", "write.plan", log(2 * C),
                              rc, B)

    small = F2Config(hot_index_size=E, hot_capacity=C, hot_mem=C // 4,
                     cold_capacity=C, cold_mem=C // 4, rc_capacity=R,
                     value_width=V)
    big = dataclasses.replace(small, hot_capacity=2 * C)
    fits = probe_engine.engine_choices(small, B)
    over = probe_engine.engine_choices(big, B + K.LANES)
    assert {c["backend"] for c in fits.values()} == {"fused_pallas"}
    assert fits["read.hot"]["reason"].startswith("fits")
    assert over["read.hot"]["backend"] == "fused_ref"
    assert "exceed the VMEM budget" in over["read.hot"]["reason"]
    assert over["read.cold"]["backend"] == "fused_pallas"   # cold log fits
    assert "SMEM" in over["write.plan"]["reason"]


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    """`JAX_COMPILATION_CACHE_DIR` wins and nothing is set in code;
    otherwise the cache goes to the fixed `.jax_cache/` of the checkout."""
    from repro import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv(compile_cache.ENV)
        path = compile_cache.enable()
        assert path == str(compile_cache.DEFAULT_DIR)
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
