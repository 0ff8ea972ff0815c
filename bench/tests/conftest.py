"""Helpers for the benchmark's own tests: the cells of BENCHMARK.json cut
to a size the CPU runs in seconds.  Run with

    JAX_PLATFORMS=cpu python -m pytest bench/tests
"""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

# the paper's split (`make_f2_config`) at 2^14 keys and 8 B values
TINY_KEYS = 1 << 14
TINY_STORE = {"hot_index_size": 1024, "hot_capacity": 4096, "hot_mem": 1024,
              "cold_capacity": 32768, "cold_mem": 64, "n_chunks": 512,
              "chunk_slots": 32, "chunklog_capacity": 2048,
              "chunklog_mem": 32, "rc_capacity": 512, "value_width": 2,
              "chain_max": 48}


def tiny_cell(name: str):
    from bench.lib.spec import cell_spec
    c = cell_spec(name, ROOT)
    c.config = dict(c.config, n_keys=TINY_KEYS, load_lanes=256,
                    store=dict(TINY_STORE),
                    service=dict(c.config["service"], pack_lanes=256))
    c.traffic = dict(c.traffic, sessions=4, depth=128, chunk=64,
                     rate_ops_per_s=5000)
    return c


@pytest.fixture(scope="session", autouse=True)
def compile_cache(tmp_path_factory):
    """Runs in one test process share compiled programs through a
    persistent cache of their own."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


@pytest.fixture
def run_tiny(tmp_path):
    import time

    from bench.lib import cell as runner

    def run(name, seed=987654321012, seconds=2.0, trace=False, fault=None):
        return runner.run_cell(tiny_cell(name), seed, seconds, trace,
                               t_start=time.perf_counter(),
                               run_dir=tmp_path / "run", require_tpu=False,
                               fault=fault)
    return run
