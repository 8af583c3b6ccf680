"""KV pool: the most KV bytes the block pool held at once over the run,
against its usable capacity (engine counters)."""


def read(run):
    cap = run.stats.get("kv_bytes_capacity", 0.0)
    if not cap:
        return None
    return 100.0 * run.stats["kv_bytes_peak"] / cap
