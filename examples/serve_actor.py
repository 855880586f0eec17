"""Continuous-batching actor serving — thin CLI over ``repro.serve``
(DESIGN.md §13): submit N random prompts, run the slot scheduler to
completion, report prefill and decode phases separately with EXACT
token accounting.

The seed version of this file timed ``gen - 1`` decode steps but
collected ``gen`` tokens into the throughput number; here every token
is attributed to exactly one phase — one prefill token per admission,
one decode token per busy slot per step — and the closed-form identity
``admissions + decoded_tokens == requests × gen`` is asserted before
anything is printed or emitted.

    PYTHONPATH=src python examples/serve_actor.py --arch granite_8b --smoke \
        --requests 8 --slots 4 --gen 16 --emit-json serve_report.json
"""

import argparse
import json
import sys

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite_8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=8,
                    help="number of prompts to serve")
    ap.add_argument("--slots", type=int, default=4,
                    help="continuous-batching decode slots")
    ap.add_argument("--prompt-len", type=int, default=12,
                    help="max prompt length (lengths sampled 1..this)")
    ap.add_argument("--gen", type=int, default=16,
                    help="generated tokens per request")
    ap.add_argument("--buckets", default=None,
                    help="comma-separated prompt padding buckets "
                         "(default: prompt-len and its half)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="KV cache length (default: prompt-len + gen)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--emit-json", default=None, metavar="FILE",
                    help="write the phase-separated serving report")
    args = ap.parse_args()

    import jax

    from repro import compile_cache
    from repro.configs import get_config
    from repro.models import backbone
    from repro.serve import ActorServeConfig, ActorServer, SUPPORTED_FAMILIES

    compile_cache.enable()
    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.family not in SUPPORTED_FAMILIES:
        print(f"{cfg.name}: family {cfg.family!r} is not servable — the "
              f"continuous-batching engine needs a position-indexed KV "
              f"cache (supported: {', '.join(SUPPORTED_FAMILIES)})",
              file=sys.stderr)
        return 2

    max_len = args.max_len or (args.prompt_len + args.gen)
    if args.buckets:
        buckets = tuple(int(b) for b in args.buckets.split(","))
    else:
        buckets = tuple(sorted({max(1, args.prompt_len // 2),
                                args.prompt_len}))
    params = backbone.init_params(cfg, jax.random.PRNGKey(args.seed))
    server = ActorServer(cfg, params, ActorServeConfig(
        slots=args.slots, max_len=max_len, buckets=buckets,
        max_new_tokens=args.gen))

    rng = np.random.RandomState(args.seed)
    lens = rng.randint(1, args.prompt_len + 1, size=args.requests)
    handles = [server.submit(rng.randint(0, cfg.vocab_size, size=int(n)))
               for n in lens]
    server.drain(timeout=600)
    completions = [h.result(0) for h in handles]

    s = server.stats()
    # exact accounting: every generated token belongs to exactly one phase
    generated = sum(len(c.tokens) for c in completions)
    assert generated == args.requests * args.gen, (generated, args.requests,
                                                   args.gen)
    assert s["generated_tokens"] == generated, (s["generated_tokens"],
                                                generated)
    prefill_tokens = s["admissions"]          # one first-token per prefill
    decode_tokens = s["decoded_tokens"]
    prefill_s, decode_s = s["prefill_s"], s["decode_s"]

    print(f"{cfg.name}: served {args.requests} requests × {args.gen} tokens "
          f"on {args.slots} slots (buckets {buckets}, "
          f"{s['prime_compiles']} prefill compiles, "
          f"{s['decode_compiles']} decode compile)")
    print(f"prefill: {prefill_tokens} prompts "
          f"({int(np.sum(lens))} prompt tokens) in {prefill_s*1e3:.1f} ms "
          f"— {prefill_tokens/prefill_s:.1f} first-tokens/s"
          if prefill_s > 0 else "prefill: instantaneous")
    print(f"decode:  {s['steps']} steps, {decode_tokens} tokens in "
          f"{decode_s*1e3:.1f} ms — {decode_tokens/decode_s:.1f} tok/s"
          if decode_s > 0 else "decode: no steps")
    if "latency_p50_ms" in s:
        print(f"latency: p50 {s['latency_p50_ms']:.1f} ms, "
              f"p99 {s['latency_p99_ms']:.1f} ms")
    print("sample tokens:", completions[0].tokens[:16])

    if args.emit_json:
        report = {
            "arch": cfg.name,
            "requests": args.requests,
            "slots": args.slots,
            "gen": args.gen,
            "buckets": list(buckets),
            "prefill": {
                "prompts": int(prefill_tokens),
                "prompt_tokens": int(np.sum(lens)),
                "first_tokens": int(prefill_tokens),
                "seconds": round(prefill_s, 6),
            },
            "decode": {
                "steps": int(s["steps"]),
                "tokens": int(decode_tokens),
                "seconds": round(decode_s, 6),
                "tokens_per_s": (round(decode_tokens / decode_s, 2)
                                 if decode_s > 0 else None),
            },
            "generated_tokens": int(generated),
            "latency_p50_ms": s.get("latency_p50_ms"),
            "latency_p99_ms": s.get("latency_p99_ms"),
            "prime_compiles": int(s["prime_compiles"]),
        }
        with open(args.emit_json, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        print(f"# wrote {args.emit_json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
