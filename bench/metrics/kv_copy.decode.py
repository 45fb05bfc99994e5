"""kv_copy.decode: device time of the ops that only move or re-lay data
(copies and transposes) inside the decode attention ops' ProgramOp
scopes (``decode_attention.*``) over the traced steps' device-busy
time, in %."""

COPY_KINDS = ("copy", "copy-start", "copy-done", "transpose")


def read(run):
    tr = run.trace
    if tr is None or not tr.steps:
        return None
    busy = tr.step_busy_s()
    if busy <= 0:
        return None
    return 100.0 * tr.scoped_seconds("decode_attention.", COPY_KINDS) / busy
