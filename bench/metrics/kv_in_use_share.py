"""Share of the reserved KV positions (slots x KV extent) that hold written
tokens, averaged over the window's decode dispatches: a prefilling slot
counts its prompt tokens written so far, a decoding slot its cursor. From
the engine's counters. Nothing where the engine counts no KV. Moves
output_tokens_per_s."""


def read(w):
    kv = w.stats.get("kv")
    return 100.0 * kv["in_use_share_mean"] if kv else None
