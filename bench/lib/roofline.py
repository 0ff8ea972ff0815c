"""The least bytes the store's work must move, computed from the ops.

Whatever implements a point operation, it has to read the op's inputs,
find the key through one index entry, move the record's value words and
return the op's outputs.  Every word is 4 bytes.  Per op, with
`value_width` V value words:

  read    key in, one index entry, the record's key to compare, V value
          words read, the status and V value words out:    4 + 2V words
  upsert  key and op in, V value words in, one index entry, V value
          words written, the status out:                   4 + 2V words
  rmw     key and op in, V words of input, one index entry, V value words
          read and V written, the status and V words out:  4 + 4V words
  delete  key and op in, one index entry, the record's meta word written,
          the status out:                                  5 words

A lane that carries no op moves nothing.

No implementation of the same work can move less, so a share of the
roofline counted from these bytes holds whichever engine runs a site.
"""
from __future__ import annotations

from .ops import OP_DELETE, OP_NOOP, OP_READ, OP_RMW, OP_UPSERT

WORD = 4


def op_bytes(op: int, value_width: int) -> int:
    v = value_width
    words = {OP_NOOP: 0, OP_READ: 4 + 2 * v, OP_UPSERT: 4 + 2 * v,
             OP_RMW: 4 + 4 * v, OP_DELETE: 5}[op]
    return WORD * words


def least_bytes(ops_executed: dict, value_width: int) -> int:
    """Least bytes of the ops executed, given as {op code: count}."""
    return sum(n * op_bytes(op, value_width)
               for op, n in ops_executed.items() if n)


def roofline_share_pct(bytes_needed: float, seconds: float,
                       bytes_per_s: float) -> float:
    """Least time the chip could take for `bytes_needed` over the time
    the work took, in percent (a point operation does no arithmetic worth
    counting, so the memory bound is the roofline)."""
    return 100.0 * (bytes_needed / bytes_per_s) / seconds
