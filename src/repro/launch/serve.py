"""Serving driver: paged-KV continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-3b \
      --requests 8 --max-new 12

``--preset tiny`` (the default) serves a 4-layer, d_model 128 cut of the
architecture, small enough for a CPU; ``--preset full`` serves it at its
registered size (llama3.2-3b: 28 layers, d_model 3072, bf16 parameters of
about 7.2 GB), which needs an accelerator.  Weights are random from a seed.

Durable-artifact round trip (cold-start AOT serving):

  # process A: build the model, serve, export the compiled step + transport
  python -m repro.launch.serve --arch llama3.2-3b --export-artifact /tmp/art

  # process B (fresh): adopt manifest.json, deserialize serve_step.bin,
  # serve identical traffic with ZERO retrace (no model build, no jit)
  python -m repro.launch.serve --from-artifact /tmp/art
"""
from __future__ import annotations

import argparse
import json
import os
import time

import jax

from repro.launch.compile_cache import use_persistent_cache
from repro.launch.train import preset_config
from repro.models.model_zoo import build_model
from repro.serving.engine import ServingEngine


def _serve_traffic(engine: ServingEngine, cfg, requests: int, max_new: int,
                   tag: str) -> None:
    rids = []
    for i in range(requests):
        prompt = [1 + (i * 7 + j) % (cfg.vocab_size - 1)
                  for j in range(4 + i % 5)]
        rids.append(engine.submit(prompt, max_new=max_new))

    t0 = time.time()
    results = engine.run_until_drained()
    dt = time.time() - t0
    total_tokens = sum(len(v) for v in results.values())
    for rid in rids:
        print(f"[{tag}] request {rid}: {results[rid]}")
    print(f"[{tag}] {len(results)} requests, {total_tokens} tokens "
          f"in {dt:.1f}s ({total_tokens / dt:.1f} tok/s)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="llama3.2-3b")
    ap.add_argument("--preset", default="tiny", choices=["tiny", "full"])
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--export-artifact", metavar="DIR", default=None,
                    help="after serving, export the jitted serve step + "
                         "RPC manifest + params as a cold-start artifact")
    ap.add_argument("--from-artifact", metavar="DIR", default=None,
                    help="cold start: adopt the artifact's manifest and "
                         "serve from its serialized step (no model build, "
                         "no retrace)")
    args = ap.parse_args(argv)
    use_persistent_cache()

    if args.from_artifact:
        with open(os.path.join(args.from_artifact, "engine.json")) as f:
            meta = json.load(f)
        cfg = preset_config(meta["arch"], meta["preset"])
        engine = ServingEngine.from_artifact(args.from_artifact, cfg)
        assert engine._step_source == "artifact"
        print(f"[serve] cold start from {args.from_artifact} "
              f"(arch={meta['arch']}, no retrace)")
        _serve_traffic(engine, cfg, args.requests, args.max_new, "serve")
        return

    cfg = preset_config(args.arch, args.preset)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params, batch_slots=args.batch_slots,
                           max_len=256, page_size=args.page_size)
    _serve_traffic(engine, cfg, args.requests, args.max_new, "serve")

    if args.export_artifact:
        engine.export_artifact(
            args.export_artifact,
            extra_meta={"arch": args.arch, "preset": args.preset})
        print(f"[serve] artifact exported to {args.export_artifact}")


if __name__ == "__main__":
    main()
