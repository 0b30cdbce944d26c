"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of stdout is one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` and, when traced, `breakdown`.  With
`--trace 0` the metrics are the cell's end-to-end metrics, with
`--trace 1` its per-layer metrics.  Every earlier line is a JSON record
for a reader (losses, step-time spread, compile seconds, cache hits).

This file knows no cell, configuration, job or metric by name.  It
reads `BENCHMARK.json` and finds, under the manifest's `paths`:

    workloads/<cell>.json         the cell's job and that job's parameters
    jobs/<job>.py                 run(spec) -> what happened
    reference/<name>.py           the configuration's plain reference
    layer_metrics/<metric>.py     compute(observed) -> number, or None

It needs a TPU and as many chips as the cell asks for, and exits
non-zero without a result otherwise: there is no CPU fallback on the
measuring path.  `--rehearse` drives the same code on CPU devices for
the tests; it prints counts and correctness, and null for every number
that only a chip can give.
"""

import time

T0 = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
# a metric from one of these sources is a reading of the chip
DEVICE_SOURCES = ("host_clock", "device_trace", "program_span")


class Finder:
    """Finds the files of a cell by name under the manifest's paths."""

    def __init__(self, manifest_path: str, paths):
        base = os.path.dirname(os.path.abspath(manifest_path))
        roots = [os.path.join(base, p) for p in paths] + [HERE]
        self.roots = list(dict.fromkeys(os.path.normpath(r) for r in roots))
        self.base = base

    def path(self, kind: str, filename: str) -> str:
        tried = [os.path.join(r, kind, filename) for r in self.roots]
        for candidate in tried:
            if os.path.isfile(candidate):
                return candidate
        raise FileNotFoundError(
            f"no {kind} file {filename!r}: looked for {', '.join(tried)}")

    def json(self, kind: str, name: str) -> dict:
        with open(self.path(kind, name + ".json")) as f:
            return json.load(f)

    def module(self, kind: str, name: str):
        path = self.path(kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"_bench_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module


def emit(**record) -> None:
    """A record for a reader, stamped with the seconds since the start."""
    print(json.dumps({"at_s": round(time.perf_counter() - T0, 3), **record}),
          flush=True)


def _named(entries, name: str, what: str) -> dict:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SystemExit(f"no {what} named {name!r} in the manifest "
                     f"(has: {[e['name'] for e in entries]})")


def _of_cell(metrics, cell: str) -> list:
    return [m for m in metrics if cell in m.get("workloads", [cell])]


class CacheEvents:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self, jax):
        self.counts = {"hits": 0, "misses": 0}
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        kind = event.rsplit("cache_", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and kind in self.counts:
            self.counts[kind] += 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--manifest",
                    default=os.path.join(REPO, "BENCHMARK.json"))
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU devices, for the tests: no device number")
    args = ap.parse_args(argv)

    with open(args.manifest) as f:
        manifest = json.load(f)
    cell = _named(manifest["workloads"], args.workload, "workload")
    find = Finder(args.manifest, manifest["paths"])
    with open(os.path.join(find.base, _named(
            manifest["configs"], cell["config"], "configuration")["file"])) as f:
        config = json.load(f)
    workload = find.json("workloads", cell["name"])
    for key in ("config", "chips"):
        if workload[key] != cell[key]:
            raise SystemExit(
                f"{cell['name']}: {key} is {workload[key]!r} in its workload "
                f"file and {cell[key]!r} in the manifest")
    end_to_end = _of_cell(manifest["end_to_end"], cell["name"])
    per_layer = _of_cell(manifest["per_layer"], cell["name"])
    # every file the cell names is found before anything is built
    sys.path.insert(0, REPO)
    job = find.module("jobs", workload["job"])
    readers = {m["name"]: find.module("layer_metrics", m["name"])
               for m in per_layer} if args.trace else {}

    import jax

    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", cell["chips"])
    devices = jax.devices()
    if not args.rehearse and devices[0].platform != "tpu":
        print(f"{cell['name']} needs a TPU; jax.devices() is {devices}",
              file=sys.stderr)
        return 1
    if len(devices) < cell["chips"]:
        print(f"{cell['name']} needs {cell['chips']} chip(s); "
              f"jax.devices() is {devices}", file=sys.stderr)
        return 1
    devices = devices[:cell["chips"]]
    cache = CacheEvents(jax)
    if not args.rehearse:
        from apex_tpu.utils.compile_cache import enable_compile_cache

        cache_dir = enable_compile_cache()
        # keep the small programs of set-up too, not only the step
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        emit(phase="env", compile_cache_dir=cache_dir, jax=jax.__version__)

    result = job.run(types.SimpleNamespace(
        name=cell["name"], config=config, workload=workload,
        devices=devices, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse, t0=T0,
        out_dir=os.path.join(HERE, "out", cell["name"]), emit=emit,
        load=find.module))
    emit(phase="compile_cache", **cache.counts)

    observed = result["observed"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": result["memory_peak_bytes"]}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": {}, "device": device}
    if args.trace:
        from benchmarks.lib import trace

        observed["trace"] = None
        events = {"devices": {}}
        if observed.get("xplane"):
            events = trace.read_xplane(observed["xplane"])
            emit(phase="trace", file=observed["xplane"],
                 lines=events["lines"])
        if events["devices"]:
            observed["trace"] = reduced = trace.reduce(events)
            first = reduced["first"]
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            line["breakdown"] = {"device_ops": trace.top(
                trace.by_label(first["ops"], events["labels"])),
                                 "idle_gaps": trace.top(first["idle_gaps"])}
        values = {name: reader.compute(observed)
                  for name, reader in readers.items()}
        metrics = per_layer
    else:
        values = result["end_to_end"]
        metrics = end_to_end
        missing = [m["name"] for m in metrics if values.get(m["name"]) is None]
        if missing:
            raise SystemExit(f"{cell['name']}: the job reported no {missing}")
    for m in metrics:
        value = values.get(m["name"])
        if value is None:
            continue   # a reader that found nothing to read
        if args.rehearse and m["source"] in DEVICE_SOURCES:
            value = None   # not measured: a CPU is not the device
        line["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
