"""Share of the reserved KV positions (slots x KV extent) that a decode
micro-step reads, averaged over the window's decode dispatches: on the
flash-decode kernel's path the positions of the tiles that cover each
decoding row's live range (a windowed layer's from its lower bound),
averaged over layers; every slot's whole extent where the decode reads it
all. From the engine's counters. Nothing where the engine counts no KV
reads. Moves tpot_p50_ms."""


def read(w):
    kv = w.stats.get("kv") or {}
    share = kv.get("read_share_mean")
    return None if share is None else 100.0 * share
