"""The plain reference: a numpy model of the store's per-round batch
semantics, independent of the code under test.

One round is one batch.  Its reads observe the state at round entry; its
writes then apply in lane order.  An upsert sets a key's value, a delete
removes the key, and an RMW adds its value to the key's current value (0
when absent).  An RMW reports CREATED when its key was absent at round
entry and no upsert or delete of the same key is in the round (the store
reports per key group); every other write reports OK, a read OK or
NOT_FOUND, and a NOOP lane NONE.

Copied from the repository's chip smoke model; the common round with only
reads and upserts runs vectorised.
"""
from __future__ import annotations

import numpy as np

from .ops import (OP_DELETE, OP_READ, OP_RMW, OP_UPSERT, ST_CREATED,
                  ST_NOT_FOUND, ST_OK)


class Model:
    def __init__(self, n_keys: int, value_width: int):
        self.vals = np.zeros((n_keys, value_width), np.int32)
        self.present = np.zeros(n_keys, bool)

    def load(self, keys: np.ndarray, vals: np.ndarray) -> None:
        self.vals[keys] = vals
        self.present[keys] = True

    def batch(self, keys, ops, vals):
        """One round: (status int32 [B], read values int32 [B, V])."""
        B = len(keys)
        status = np.zeros(B, np.int32)
        rvals = np.zeros((B, self.vals.shape[1]), np.int32)
        rd = ops == OP_READ
        hit = self.present[keys[rd]]
        status[rd] = np.where(hit, ST_OK, ST_NOT_FOUND)
        rvals[rd] = np.where(hit[:, None], self.vals[keys[rd]], 0)
        up = ops == OP_UPSERT
        if not np.any((ops == OP_RMW) | (ops == OP_DELETE)):
            idx = np.flatnonzero(up)
            # the last upsert of a key in lane order wins
            _, last = np.unique(keys[idx][::-1], return_index=True)
            win = idx[::-1][last]
            self.vals[keys[win]] = vals[win]
            self.present[keys[win]] = True
            status[up] = ST_OK
            return status, rvals
        setk = set(keys[up | (ops == OP_DELETE)].tolist())
        absent0 = {int(k): not self.present[k] for k in keys}
        for i in np.flatnonzero(~rd & (ops != 0)):
            k, op = int(keys[i]), int(ops[i])
            status[i] = ST_OK
            if op == OP_UPSERT:
                self.vals[k], self.present[k] = vals[i], True
            elif op == OP_DELETE:
                self.present[k] = False
            elif op == OP_RMW:
                if k not in setk and absent0[k]:
                    status[i] = ST_CREATED
                base = self.vals[k] if self.present[k] else 0
                self.vals[k], self.present[k] = base + vals[i], True
        return status, rvals


def mismatched(ops, got_status, got_vals, want_status, want_vals):
    """bool [B]: lanes whose status differs, or OK reads whose value
    differs."""
    bad = got_status != want_status
    read_ok = (ops == OP_READ) & (want_status == ST_OK)
    return bad | (read_ok & np.any(got_vals != want_vals, axis=1))
