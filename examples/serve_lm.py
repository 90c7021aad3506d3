"""Serving example: batched requests with the cost-based KV prefix cache.

Runs two traffic mixes through the engine — with and without a shared
system prompt — and shows the prefill tokens the paper-adapted page cache
saves.

  PYTHONPATH=src python examples/serve_lm.py
"""
import jax
import numpy as np

from repro.configs import get, reduced
from repro.models.model import init_params
from repro.serve.engine import Request, ServingEngine


def main():
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = reduced(get("qwen1.5-0.5b"), d_model=64, n_periods=2, vocab=512)
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)

    system = rng.integers(1, cfg.vocab_size, 32).tolist()
    shared = [Request(i, system + rng.integers(1, cfg.vocab_size, 8).tolist(),
                      max_new_tokens=4) for i in range(6)]
    cold = [Request(100 + i,
                    rng.integers(1, cfg.vocab_size, 40).tolist(),
                    max_new_tokens=4) for i in range(6)]

    for name, reqs in (("shared system prompt", shared),
                       ("cold unrelated prompts", cold)):
        engine = ServingEngine(cfg, params, slots=3, max_len=96,
                               page_size=8, cache_budget_pages=32,
                               policy="cost")
        done = engine.run(list(reqs))
        st = engine.stats
        print(f"{name}: served {len(done)}; prompt tokens "
              f"{st.prompt_tokens}, prefill saved by cache "
              f"{st.prefill_saved} ({st.prefill_saved/st.prompt_tokens:.0%})")
        print("  sample generation:", done[0].generated)


if __name__ == "__main__":
    main()
