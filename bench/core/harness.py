"""One run of one cell: set up, serve the window, measure, check.

``run_cell`` is what ``bench/run.py`` calls.  Tests call it too, with
``require_chip=False`` and a small configuration, to drive a whole run on
the CPU.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
from typing import Callable, Dict, Optional

import jax

from . import check, counts, driver, stats, traffic, weights
from .trace import Trace, attribute, find_xplane, self_times

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
OUT_DIR = os.path.join(ROOT, ".bench_out")


class NoChip(SystemExit):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str, bench_json: Optional[str] = None) -> Dict:
    """The cell's entries in BENCHMARK.json and the files they name."""
    spec = load_json(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    wl = next((w for w in spec["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    return dict(
        spec=spec, workload=wl,
        config=load_json(os.path.join(ROOT, cfg_entry["file"])),
        cell=load_json(os.path.join(BENCH, "workloads", workload + ".json")),
        mix=load_json(os.path.join(BENCH, "traffic", wl["traffic"] + ".json")),
    )


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(BENCH, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def device_info(chips: int, require_chip: bool) -> dict:
    devs = jax.devices()
    d0 = devs[0]
    if require_chip and (d0.platform != "tpu" or len(devs) < chips):
        raise NoChip(f"this cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devs)} {d0.platform} device(s)")
    return dict(platform=d0.platform, kind=d0.device_kind, count=chips)


def _metrics_for(spec: dict, workload: str, kind: str):
    out = []
    for m in spec[kind]:
        if "workloads" in m and workload not in m["workloads"]:
            continue
        out.append(m)
    return out


def load_reader(name: str) -> Callable:
    path = os.path.join(BENCH, "metrics", name + ".py")
    sp = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def end_to_end(rec: "driver.RunRecord", metrics: list) -> Dict:
    t_open, t_close = rec.window
    win = [o for o in rec.outcomes.values() if o.phase == "win"]
    out = {}
    for m in metrics:
        name = m["name"]
        if name == "setup_s":
            v = rec.setup_s
        elif name in ("latency_p50_ms", "latency_p95_ms"):
            lat = stats.latency_sample(win, t_close + traffic.POST_WINDOW_S)
            q = 50 if name.endswith("p50_ms") else 95
            v = 1e3 * stats.percentile(lat, q)
        elif name == "out_tok_per_s":
            n = sum(len(o.tokens) for o in rec.outcomes.values()
                    if o.reason is None and o.end is not None
                    and t_open <= o.end <= t_close)
            v = n / (t_close - t_open)
        else:
            raise SystemExit(f"no rule for end-to-end metric {name!r}")
        out[name] = {"value": v, "unit": m["unit"]}
    return out


def work_model(rec: "driver.RunRecord", d: dict, out_tokens: int,
               chunk: int) -> "counts.StepWork":
    """Work per global fused step.  A request's admission step is put at
    the middle of the run() call it drained in, less its life in steps
    (the program reports completion per call, not per step)."""
    k = rec.steps_per_call
    w = counts.StepWork(d)
    for o in rec.outcomes.values():
        if o.reason is not None or o.call_done is None:
            continue
        life = len(counts.life_steps(o.prompt_len, out_tokens, chunk))
        done_step = o.call_done * k + k // 2
        w.add_request(done_step - life + 1, o.prompt_len, out_tokens, chunk)
    return w


def per_layer(metrics: list, ctx: dict) -> Dict:
    out = {}
    for m in metrics:
        v = load_reader(m["name"])(ctx)
        if v is None:
            continue
        out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def breakdown(tr: Trace, win, dev: int = 0) -> Dict:
    """The ten device operations with the most self time, and the idle
    gaps of the device by the host span they fell in."""
    own = self_times(tr.ops.get(dev, []), win)
    ops = sorted(own.items(), key=lambda kv: -kv[1])[:10]
    gaps = attribute(tr.idle_gaps(dev, win), tr.spans)
    idle = sorted(gaps.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle]}


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             t_process: float, require_chip: bool = True,
             overrides: Optional[dict] = None,
             log: Callable = print) -> dict:
    S = load_spec(workload)
    if overrides:
        S.update(overrides)
    spec, wl, cfg, cell, mix = (S["spec"], S["workload"], S["config"],
                                S["cell"], S["mix"])
    dev = device_info(int(wl["chips"]), require_chip)
    peaks = peaks_for(dev["kind"]) if require_chip else None
    prog = driver.import_program()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    compiles = driver.CompileCounter()
    clock = driver.Clock()

    params = weights.make(cfg, seed)
    system = driver.System(prog, cfg, cell, mix, params, clock)
    del params
    reqs = traffic.generate(mix, seed, seconds, system.arch.vocab_size,
                            system.flows, system.flow_reject)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, "trace", f"{workload}.{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    rec = driver.serve(system, mix, cell, reqs, seconds, t_process,
                       compiles, trace_dir=trace_dir, log=log)
    win = [o for o in rec.outcomes.values() if o.phase == "win"]
    dev["memory_peak_bytes"] = rec.memory_peak_bytes
    log(f"[bench] window requests {len(win)}; served "
        f"{sum(o.reason is None and o.end is not None for o in win)}; "
        f"gate-rejected {sum(o.reason == 'gate-reject' for o in win)}; "
        f"run() calls {len(rec.calls)}")
    log(f"[bench] compilations inside the window: {rec.compiles_in_window} "
        f"({rec.compile_s_in_window:.3f} s)")
    if rec.lag:
        log(f"[bench] generator lag p95: "
            f"{1e3 * stats.percentile(rec.lag, 95):.1f} ms over "
            f"{len(rec.lag)} sends (sent at the next run() boundary)")
    else:
        log("[bench] generator lag p95: closed loop, each request is sent "
            "at the run() boundary after its predecessor finished")

    result_metrics: Dict = {}
    bd = None
    if trace:
        d = weights.dims(cfg)
        tr = Trace.load(find_xplane(trace_dir))
        first, last = rec.trace_calls
        # the trace holds every serving call: call i is run span i
        twin = tr.window(first, last - first + 1)
        if twin is None:
            raise RuntimeError("the trace holds no bench.run span")
        busy = tr.busy(0, twin)
        chunk = int(cell["batcher"]["prefill_chunk"])
        k = rec.steps_per_call
        ctx = dict(trace=tr, window=twin, dev=0, busy=busy, peaks=peaks,
                   dims=d, chips=int(wl["chips"]),
                   work=work_model(rec, d, int(mix["output_tokens"]), chunk),
                   steps=(first * k, (last + 1) * k), log=log)
        result_metrics = per_layer(
            _metrics_for(spec, workload, "per_layer"), ctx)
        dev["busy_s"] = busy
        dev["window_s"] = twin[1] - twin[0]
        bd = breakdown(tr, twin)
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        result_metrics = end_to_end(
            rec, _metrics_for(spec, workload, "end_to_end"))

    # --- correctness, after the window, with the program's state freed
    system.free()
    del system
    attempted = len(win)
    failed = stats.failed_count(win)
    params = weights.make(cfg, seed)
    checks = check.checks(cfg, params, cell, mix, win, rec.requests, seed,
                          log=log)
    del params
    for name, c in checks.items():
        log(f"[check] {name} {c['value']} limit {c['limit']}")
    result = {"correct": check.verdict(checks), "attempted": attempted,
              "failed": failed, "metrics": result_metrics, "device": dev}
    if bd is not None:
        result["breakdown"] = bd
    result["checks"] = checks
    return result
