"""KV cache: KV positions the live slots held over the positions the
engines reserve, summed over the window's steps (program counters
``kv.positions_held`` / ``kv.positions_reserved``), in %."""


def read(rec):
    prog = rec.get("program")
    if not prog:
        return None
    c = prog["counters"]
    reserved = c.get("kv.positions_reserved", 0)
    return 100.0 * c.get("kv.positions_held", 0) / reserved \
        if reserved else None
