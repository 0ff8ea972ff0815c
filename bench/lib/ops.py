"""The store's operation and status codes, as its batch interface defines
them.  Copied here so that the reference imports nothing of the program."""

OP_NOOP, OP_READ, OP_UPSERT, OP_RMW, OP_DELETE = 0, 1, 2, 3, 4
ST_NONE, ST_OK, ST_NOT_FOUND, ST_CREATED = 0, 1, 2, 3
WRITE_OPS = (OP_UPSERT, OP_RMW, OP_DELETE)
