"""Continuous-batching serving demo: a mixed-length request trace through the
slot-refilling engine, with the fixed-batch loop run on the same trace for
contrast.  Early-finishing slots are re-admitted from the queue the very next
decode tick, so the compressed-weight stream (the decode-regime cost the
paper's N:M format minimizes) is shared by more useful tokens per pass.

Run:  PYTHONPATH=src python examples/serve_continuous.py --arch llama3.2-1b
"""

import argparse
import dataclasses

import jax

from repro.configs import get_config
from repro.models import init_model
from repro.serve import ServeEngine, serve_sequential, synthetic_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-mix", default="12,4,8,3",
                    help="comma list of gen budgets cycled over the trace")
    ap.add_argument("--arrival-every", type=int, default=0)
    ap.add_argument("--impl", default="xla",
                    help="xla | xla_gather | pallas")
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=True)
    cfg = cfg.replace(sparsity=dataclasses.replace(
        cfg.sparsity, mode="compressed", impl=args.impl))
    params, _ = init_model(jax.random.PRNGKey(0), cfg)

    gen_lens = [int(g) for g in args.gen_mix.split(",")]
    reqs = synthetic_trace(cfg, n_requests=args.requests,
                           prompt_len=args.prompt_len, gen_lens=gen_lens,
                           arrival_every=args.arrival_every)
    max_len = args.prompt_len + max(gen_lens)

    eng = ServeEngine(params, cfg, n_slots=args.slots, max_len=max_len)
    results = eng.run(reqs)
    st = eng.stats()
    print(f"arch={args.arch} slots={args.slots} requests={args.requests} "
          f"gens={gen_lens}")
    print(f"continuous: {int(st['tokens'])} tokens / "
          f"{int(st['decode_steps'])} decode steps "
          f"(occupancy {st['occupancy']:.2f})")

    _, sstats = serve_sequential(params, cfg, reqs, args.slots,
                                 max_len=max_len)
    print(f"sequential: same trace takes {int(sstats['decode_steps'])} "
          f"decode steps (finished slots idle until the batch drains)")

    for rid in sorted(results)[:4]:
        r = results[rid]
        print(f"  req{rid}: admitted t={r.admitted_at} finished t={r.finished_at} "
              f"tokens {r.tokens[:8].tolist()}")


if __name__ == "__main__":
    main()
