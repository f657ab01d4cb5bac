"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived,device`` CSV rows (derived = the table's
metric: relative error, NLL, scaling exponent, or a boolean claim check;
device = the platform, kind and count the row ran on), and can mirror them
to a JSON file (``--json``) for the CI perf-trajectory artifact.

  approx_error  -> paper Fig. 1 + Fig. 4 / Tab. 7 (error vs budget/method)
  entropy_error -> paper Fig. 5 (error vs softmax entropy)
  scaling       -> paper Tab. 7 (runtime scaling 256..4096)
  swap_eval     -> paper Tab. 1/2 (drop-in compatibility with trained weights)
  decode_bench  -> beyond-paper MRA decode (KV-block selection)
  kernel_bench  -> fwd+bwd Pallas-kernel vs jnp path timing + grad parity
  serve_bench   -> continuous-batching engine (req/s, tok/s, inter-token
                   latency p50/p99, chunked-prefill dispatch economy)
  spec_bench    -> resolution-speculative decoding (acceptance rate vs K,
                   accepted-tokens-per-dispatch, tok/s vs PR 3 baseline)

``--list`` prints the registered benchmark names (one per line) and exits,
so CI scripts enumerate instead of hard-coding.

``--mesh DxM`` (default "1": no mesh) activates a (data, model) device mesh
for the run: modules read it via ``mesh_utils.get_mesh()`` and place/shard
their inputs accordingly (decode_bench drives the shard_map TP decode path).
Use ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to validate
sharded runs on a CPU host.

The Pallas kernels compile for the TPU. ``--interpret`` runs them in
interpret mode instead; without it, a suite that needs a kernel fails on a
host without a TPU. JAX's persistent compilation cache is on
(``repro.launch.device.enable_compile_cache``).
"""
import argparse
import json
import sys

# registry: name -> module basename under benchmarks/ (kept import-free so
# ``--list`` answers without pulling in jax)
MODULES = (
    "approx_error",
    "entropy_error",
    "scaling",
    "swap_eval",
    "decode_bench",
    "kernel_bench",
    "serve_bench",
    "spec_bench",
)

# row-presence schema: beyond the per-suite "emitted anything at all" check,
# these named rows are load-bearing for the BENCH_*.json trajectory (the
# telemetry acceptance rows, DESIGN.md §13) — a refactor that silently stops
# emitting one must fail the run, not ship a quietly thinner artifact
REQUIRED_ROWS = {
    "serve_bench": (
        "serve_ttft_p50",
        "serve_ttft_p99",
        "serve_telemetry_overhead_ratio",
        "serve_cache_occupancy",
        "serve_spec_accept_per_slot",
        "serve_longctx_tok_per_s",
    ),
    "spec_bench": ("spec_base_tok_per_dispatch",),
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated module subset")
    ap.add_argument("--list", action="store_true",
                    help="print registered benchmark names and exit")
    ap.add_argument("--mesh", default="1",
                    help="device mesh 'D' or 'DxM' (default: 1 = no mesh)")
    ap.add_argument("--json", default=None,
                    help="also write results to this JSON file (CI artifact)")
    ap.add_argument("--trace", default=None,
                    help="request-lifecycle trace JSONL output path, passed "
                         "to suites that accept trace_path (serve_bench)")
    ap.add_argument("--interpret", action="store_true",
                    help="run the Pallas kernels in interpret mode (required "
                         "for kernel suites off-TPU)")
    args = ap.parse_args()

    if args.list:
        print("\n".join(MODULES))
        return

    import importlib

    from repro.distributed import mesh_utils
    from repro.launch.device import device_summary, enable_compile_cache
    from repro.launch.mesh import parse_mesh

    from .common import CSV_HEADER, emit_row

    enable_compile_cache()

    chosen = args.only.split(",") if args.only else list(MODULES)
    unknown = [n for n in chosen if n not in MODULES]
    if unknown:
        ap.error(f"unknown benchmark(s) {unknown}; --list shows the registry")
    # import only what runs: each module pulls in jax + model code
    modules = {name: importlib.import_module(f"benchmarks.{name}")
               for name in chosen}
    mesh = parse_mesh(args.mesh)

    print(CSV_HEADER)
    rows = []

    def make_emit(suite):
        def emit(name, us, derived):
            emit_row(name, us, derived)
            rows.append({"name": name, "us_per_call": us,
                         "derived": str(derived), "suite": suite})
        return emit

    import inspect

    with mesh_utils.use_mesh(mesh):
        for name in chosen:
            params = inspect.signature(modules[name].run).parameters
            kwargs = {}
            if args.trace and "trace_path" in params:
                kwargs["trace_path"] = args.trace
            if "interpret" in params:
                kwargs["interpret"] = args.interpret
            modules[name].run(make_emit(name), **kwargs)

    # schema check: every chosen suite must have emitted at least one row.
    # A partial artifact (a module silently contributing nothing — e.g. an
    # import-time skip or an exception swallowed upstream) must fail loudly
    # here rather than be committed as the perf-trajectory baseline.
    empty = [n for n in chosen if not any(r["suite"] == n for r in rows)]
    if empty:
        sys.exit(f"[bench] FATAL: suites emitted zero rows: {empty} — "
                 "refusing to produce a partial artifact")
    names = {r["name"] for r in rows}
    missing = [f"{suite}:{row}" for suite in chosen
               for row in REQUIRED_ROWS.get(suite, ())
               if row not in names]
    if missing:
        sys.exit(f"[bench] FATAL: required rows missing: {missing} — "
                 "refusing to produce a partial artifact")

    if args.json:
        meta = {"mesh": args.mesh, "modules": chosen,
                "device": device_summary(), "interpret": args.interpret}
        with open(args.json, "w") as f:
            json.dump({"meta": meta, "rows": rows}, f, indent=2)
        print(f"[bench] wrote {len(rows)} rows to {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
