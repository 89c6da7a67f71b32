"""KV pool (ops/paged_kv.py): 1 - min(free pages) / total pages over the
2 Hz samples of the window, %."""


def read(obs):
    frees = [c["serve_kv_free_pages"] for t, c in obs.samples
             if obs.lo <= t < obs.hi and "serve_kv_free_pages" in c]
    total = obs.counters_end.get("serve_kv_total_pages")
    if not frees or not total:
        return None
    return 100.0 * (1.0 - min(frees) / total)
