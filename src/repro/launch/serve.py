"""Serving launcher: licensed batched generation (Fig. 2's edge role).

Loads the production version from a WeightStore (or random-inits),
builds the tier ladder, and drains a batch of requests per tier through
the continuous-batching ``LicensedGateway`` — demonstrating one stored
weight set serving multiple accuracy tiers (§3.5).

The observability layer rides along: ``--prometheus-out`` dumps the
Prometheus text exposition, ``--trace-out`` the whole-gateway Chrome
trace (load it in Perfetto / chrome://tracing), ``--audit-out`` the
licensing audit stream as JSONL.  Pass ``-`` to print to stdout.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --tiers full,free --prompt-len 32 --new-tokens 8 \
      --prometheus-out - --trace-out trace.json --audit-out audit.jsonl
"""
from __future__ import annotations

import argparse
import sys

import jax
import numpy as np

from repro.configs import get_config, list_configs, smoke_variant
from repro.core.licensing import FULL_TIER, LicenseTier
from repro.core.weightstore import WeightStore
from repro.models import init_params
from repro.serving import LicensedGateway


def _dump(dest: str, text: str, label: str) -> None:
    if dest == "-":
        sys.stdout.write(text if text.endswith("\n") else text + "\n")
    else:
        with open(dest, "w") as f:
            f.write(text)
        print(f"wrote {label} to {dest}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(list_configs()))
    ap.add_argument("--store", default=None)
    ap.add_argument("--tiers", default="full,free")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--full-config", action="store_true")
    ap.add_argument("--no-telemetry", action="store_true",
                    help="disable tracing/metrics/audit recording")
    ap.add_argument("--prometheus-out", default=None, metavar="PATH",
                    help="dump Prometheus text exposition ('-' = stdout)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="dump Chrome trace_event JSON ('-' = stdout)")
    ap.add_argument("--audit-out", default=None, metavar="PATH",
                    help="dump licensing audit JSONL ('-' = stdout)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if not args.full_config:
        cfg = smoke_variant(cfg)
    dev = jax.devices()[0]
    print(f"serving {cfg.name} on {dev.platform} ({dev.device_kind}), "
          f"{len(jax.devices())} device(s)")

    key = jax.random.PRNGKey(args.seed)
    if args.store:
        store = WeightStore(args.store)
        template = init_params(key, cfg)
        params = store.checkout(cfg.name, template=template)
        print(f"loaded production version {store.production_version(cfg.name)}")
    else:
        params = init_params(key, cfg)

    tiers = {"full": FULL_TIER,
             "free": LicenseTier(name="free", masks={"*": ((0.0, 0.01),)})}
    gw = LicensedGateway(cfg, params, tiers=tiers, max_batch=args.batch,
                         max_prompt=args.prompt_len,
                         max_new_cap=args.new_tokens,
                         telemetry=not args.no_telemetry)

    rng = np.random.default_rng(args.seed)
    for tier in args.tiers.split(","):
        reqs = [gw.submit(rng.integers(0, cfg.vocab_size, args.prompt_len,
                                       dtype=np.int32),
                          max_new_tokens=args.new_tokens, license=tier,
                          seed=args.seed)
                for _ in range(args.batch)]
        gw.run()
        print(f"tier={tier}: " + " | ".join(str(r.out_tokens) for r in reqs[:2]))

    m = gw.metrics()
    print(f"served {m['completed']} requests, "
          f"{m['tokens_generated']} tokens; "
          f"ttft p99 {m['latency']['ttft_s']['p99'] * 1e3:.1f}ms")
    if args.prometheus_out:
        _dump(args.prometheus_out, gw.render_prometheus(), "Prometheus text")
    if args.trace_out:
        _dump(args.trace_out, gw.chrome_trace(), "Chrome trace")
    if args.audit_out:
        _dump(args.audit_out, gw.audit.render_jsonl(), "audit JSONL")


if __name__ == "__main__":
    main()
