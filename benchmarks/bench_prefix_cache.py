"""Beyond-paper integration benchmark: KV prefix-cache hit rate and
prefill-tokens-saved, cost-based (paper-adapted) vs LRU, on a multi-turn
serving trace with hot system prompts and cold scans."""
from __future__ import annotations

import numpy as np

from repro.serve.kvcache import PagedKVCacheManager


def trace(rng, n=200, vocab=1000, sys_len=48, user_len=16):
    systems = [rng.integers(1, vocab, sys_len).tolist() for _ in range(3)]
    reqs = []
    for i in range(n):
        r = rng.random()
        if r < 0.7:                      # hot multi-turn traffic
            s = systems[int(rng.integers(0, len(systems)))]
            reqs.append(s + rng.integers(1, vocab, user_len).tolist())
        else:                            # cold long one-offs
            reqs.append(rng.integers(1, vocab, sys_len + user_len).tolist())
    return reqs


def run(print_rows: bool = True):
    rng = np.random.default_rng(7)
    reqs = trace(rng)
    out = {}
    for policy in ("lru", "cost"):
        m = PagedKVCacheManager(page_size=8, budget_bytes=40 * 128,
                                page_bytes=128, policy=policy)
        hits = pages = saved = total = 0
        for i, toks in enumerate(reqs):
            r = m.allocate(i, toks)
            hits += r.hit_pages
            pages += len(r.page_ids)
            saved += len(toks) - r.recompute_tokens
            total += len(toks)
        out[policy] = {"page_hit_rate": hits / pages,
                       "prefill_saved_frac": saved / total}
        if print_rows:
            print(f"prefix_cache/{policy}/page_hit_rate,0,"
                  f"{out[policy]['page_hit_rate']:.3f}")
            print(f"prefix_cache/{policy}/prefill_saved,0,"
                  f"{out[policy]['prefill_saved_frac']:.3f}")
    if print_rows:
        adv = out["cost"]["prefill_saved_frac"] - \
            out["lru"]["prefill_saved_frac"]
        print(f"prefix_cache/cost_advantage,0,{adv:.3f}")
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    run()
