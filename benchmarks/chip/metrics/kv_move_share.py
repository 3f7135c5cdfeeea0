"""Device time of the operations under the packed step's ``kv_read``,
``kv_write`` and ``kv_carry`` scopes, at any depth of their scope path,
over the device's busy time, in the traced window: the share of the chip
spent moving keys and values."""

KV_SCOPES = ("kv_read", "kv_write", "kv_carry")


def read(r):
    if r.traced is None or r.traced.busy_ns <= 0 \
            or set(r.scope_s) <= {"none"}:
        return None
    return 100.0 * r.under(KV_SCOPES) / r.traced.busy_s
