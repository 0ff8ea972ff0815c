"""Broken variants of the timed path, to show that the check fails them.

None of these runs in a benchmark run.  The control runs on the chip
through `bench/control.py` and, at a small size, in `bench/tests`; the
faults run in `bench/tests`.  Each wraps the routed store's public
`apply_round` (the entry every session round and every load batch goes
through) or changes the deployment's configuration.

Controls (each breaks one guarantee the configuration states):
  int16_values  values kept in 16 bits: the tempting half-width value
                column.  Breaks "every acknowledged write is read back
                exactly".
  fsync_rotate  the WAL's own "rotate" flush policy: acks before fsync.
                Breaks "every acknowledged op is in the fsync'd WAL".

Faults:
  state_unchanged  each round's state update is thrown away.
  half_batch       the second half of each round's lanes is not executed.
  answer_altered   one answer of each round is changed where it is made.
(One chip has no exchange between chips to leave out.)
"""
from __future__ import annotations

import copy

from .cell import inner_store


class Fault:
    name = ""
    phase = "built"         # install after the service is built / loaded

    def configure(self, config: dict) -> dict:
        return config

    def install(self, svc, phase: str) -> None:
        if phase != self.phase:
            return
        kv = inner_store(svc)
        orig = kv.apply_round
        kv.apply_round = lambda keys, ops, vals=None: self.round(
            kv, orig, keys, ops, vals)

    def round(self, kv, orig, keys, ops, vals):
        return orig(keys, ops, vals)


class Int16Values(Fault):
    name = "int16_values"

    def round(self, kv, orig, keys, ops, vals):
        import jax.numpy as jnp
        vals = jnp.asarray(vals, jnp.int32)
        return orig(keys, ops, (vals << 16) >> 16)


class FsyncRotate(Fault):
    name = "fsync_rotate"

    def configure(self, config: dict) -> dict:
        config = copy.deepcopy(config)
        if not config.get("durability"):
            raise ValueError("fsync_rotate needs a durable configuration")
        config["durability"]["fsync"] = "rotate"
        return config

    def install(self, svc, phase: str) -> None:
        pass


class StateUnchanged(Fault):
    name = "state_unchanged"
    phase = "loaded"

    def round(self, kv, orig, keys, ops, vals):
        import jax
        import jax.numpy as jnp
        kept = jax.tree.map(jnp.copy, kv.state)
        out = orig(keys, ops, vals)
        kv.state = kept
        return out


class HalfBatch(Fault):
    name = "half_batch"

    def round(self, kv, orig, keys, ops, vals):
        import jax.numpy as jnp
        ops = jnp.asarray(ops, jnp.int32)
        half = jnp.arange(ops.shape[0]) >= ops.shape[0] // 2
        return orig(keys, jnp.where(half, jnp.int32(0), ops), vals)


class AnswerAltered(Fault):
    name = "answer_altered"
    phase = "loaded"

    def round(self, kv, orig, keys, ops, vals):
        status, rvals, placed, deferred = orig(keys, ops, vals)
        return status, rvals.at[0].add(1), placed, deferred


CONTROLS = {f.name: f for f in (Int16Values, FsyncRotate)}
FAULTS = {f.name: f for f in (StateUnchanged, HalfBatch, AnswerAltered)}


def by_name(name: str) -> Fault:
    cls = {**CONTROLS, **FAULTS}[name]
    return cls()
