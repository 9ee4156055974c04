"""Device program: the share of the key axis's padded slots that hold a
live key, over the window's device batches (the flight records'
``live_keys`` over their ``key_slots``).  None where the records carry no
``key_slots``."""


def read(ctx):
    records = [r for r in ctx.get("flight") or []
               if r.get("op") == "bls_verify" and not r.get("host_fallback")
               and r.get("key_slots")]
    if not records:
        return None
    return 100.0 * (sum(r["live_keys"] for r in records)
                    / sum(r["key_slots"] for r in records))
