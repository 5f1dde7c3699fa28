"""Run one benchmark cell on the accelerator and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  Everything the run needs is found by
name from ``BENCHMARK.json``: the cell names a configuration
(``bench/configs/``), a traffic mix (``bench/traffic/<mix>.json``) and,
through the per-layer metrics that list it, one reader per metric
(``bench/metrics/<metric>.py``).

One run is one process.  It finds the chips the cell asks for or exits
non-zero without a result; builds the deployment from the seed; warms
every shape the traffic uses; drives the traffic for ``--seconds``
through one ``GraphClient`` per session; then checks every acknowledged
update, every answer and the final graphs against the host reference.
With ``--trace 1`` the window runs under the profiler and the per-layer
metrics are printed instead of the end-to-end ones.  The last line of
standard output is the result as one JSON object.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
JOIN_GRACE_S = 120.0


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- lookup ---


def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def resolve(spec: dict, name: str, root: Path = ROOT) -> dict:
    """The cell ``name`` with its configuration, traffic and the metrics
    it reports, all read from the files their names point to."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{cell['traffic']}.json") as f:
        traffic = json.load(f)

    def mine(metrics):
        return [m for m in metrics if name in m.get("workloads", [name])]

    return {"cell": cell, "config": config, "traffic": traffic,
            "end_to_end": mine(spec["end_to_end"]),
            "per_layer": mine(spec["per_layer"])}


def metric_reader(name: str, root: Path = ROOT):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    import importlib.util
    path = root / "bench" / "metrics" / f"{name}.py"
    mod_spec = importlib.util.spec_from_file_location(
        f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------ the device ---


def devices_or_exit(chips: int):
    """The accelerator's devices; exits non-zero, printing no result, when
    JAX finds no TPU or fewer chips than the cell asks for."""
    try:
        import jax
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"bench: JAX found no device ({e}); nothing was run")
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU (JAX platform {devices[0].platform!r}); "
                 f"the benchmark runs only on the accelerator")
    if len(devices) < chips:
        sys.exit(f"bench: the cell asks for {chips} chips, JAX finds "
                 f"{len(devices)}")
    return devices[:chips]


def enable_compile_cache(path: Path = CACHE_DIR):
    """JAX's persistent cache at a fixed path in the checkout, keeping
    every program however quickly it compiled."""
    import jax
    path.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs compiled or read from the persistent cache, as JAX's
    monitoring reports them: the count should not move inside a window."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._listen)

    def _listen(self, event, duration, **_):
        if event == self._EVENT:
            self.n += 1
            self.seconds += duration


# ------------------------------------------------------------ the window ---


class Record:
    """One request as the client saw it."""
    __slots__ = ("req", "t_submit", "t_done", "value", "gen", "error")

    def __init__(self, req, t_submit, t_done, value, gen, error=None):
        self.req = req
        self.t_submit, self.t_done = t_submit, t_done
        self.value, self.gen, self.error = value, gen, error


def build_ops(req):
    """The typed ops of one request, and for a read the (kind, start,
    stop) span of each query kind among them."""
    from repro.api import CommunityOf, SameSCC, updates_from_arrays
    if req.kind == "update":
        return updates_from_arrays(*req.ops), None
    ops, spans = [], []
    for kind, arrs in req.queries.items():
        start = len(ops)
        if kind == "same_scc":
            ops += [SameSCC(int(a), int(b)) for a, b in zip(*arrs)]
        elif kind == "community_of":
            ops += [CommunityOf(int(a)) for a in arrs[0]]
        else:
            raise ValueError(f"unknown query kind {kind!r}")
        spans.append((kind, start, len(ops)))
    return ops, spans


def submit(client, ops, spans):
    """Send one request through ``GraphClient.submit_many``; returns the
    acknowledgements and the commit generation (update), or the answers
    and generations of each query kind (read)."""
    res = client.submit_many(ops)
    if spans is None:
        return (np.fromiter((r.value for r in res), bool, len(res)),
                res[0].gen if res else None)
    return ({k: ([r.value for r in res[a:b]], [r.gen for r in res[a:b]])
             for k, a, b in spans}, None)


def issue(client, req):
    return submit(client, *build_ops(req))


def drive(stack, traffic: dict, pools: list, seconds: float, annotate):
    """Run every closed-loop session against the stack for ``seconds``;
    returns the window's start and close, the records of every request
    sent, and how many sessions were still busy a grace period after the
    close."""
    n = traffic["sessions"]
    records = [[] for _ in range(n)]
    start = threading.Barrier(n + 1)
    t = {}

    def session(s):
        pool = pools[s]
        i = 0
        start.wait()
        t_end = t["end"]
        while time.perf_counter() < t_end:
            req = pool[i % len(pool)]
            ops, spans = build_ops(req)
            client = stack.client(s, req.graph)
            t_sub = time.perf_counter()
            try:
                with annotate(f"bench.{req.kind}"):
                    value, gen = submit(client, ops, spans)
                rec = Record(req, t_sub, time.perf_counter(), value, gen)
            except Exception as e:  # counted as failed, never retried
                rec = Record(req, t_sub, time.perf_counter(), None, None,
                             repr(e))
            records[s].append(rec)
            i += 1

    threads = [threading.Thread(target=session, args=(s,), daemon=True,
                                name=f"bench-session-{s}")
               for s in range(n)]
    for th in threads:
        th.start()
    t["start"] = time.perf_counter()
    t["end"] = t["start"] + seconds
    start.wait()
    deadline = t["end"] + JOIN_GRACE_S
    for th in threads:
        th.join(max(0.0, deadline - time.perf_counter()))
    hung = sum(th.is_alive() for th in threads)
    return t["start"], t["end"], [r for rs in records for r in rs], hung


# ------------------------------------------------------------- reference ---


def check(boot: list, records: list, finals: list,
          reopened: dict | None = None) -> list:
    """Every number compared, with its limit: acknowledgements replayed in
    commit order, answers at the generation each carries, the final
    graphs, requests that never came back and, for a durable store, the
    state a cold open recovers and the WAL fsyncs that preceded each
    acknowledgement."""
    from bench import reference
    failed = sum(1 for r in records if r.error is not None)
    acks = reads = stale = dup = 0
    state_gap = {"alive_differ": 0, "edges_differ": 0, "labels_differ": 0,
                 "n_ccs_differ": 0}
    durable = {f"recovered_{k}": 0 for k in state_gap}
    durable.update(recovered_gen_differ=0, acks_before_fsync=0)
    for g, (alive, keys, gen0) in enumerate(boot):
        mine = [r for r in records if r.req.graph == g and r.error is None]
        ups = sorted((r for r in mine if r.req.kind == "update"),
                     key=lambda r: r.gen)
        gens = [r.gen for r in ups]
        dup += len(gens) - len(set(gens))
        by_gen = {}
        for r in mine:
            if r.req.kind == "read":
                for kind, (vals, gs_) in r.value.items():
                    for i, gg in enumerate(gs_):
                        by_gen.setdefault(gg, []).append((r, kind, i))
        host = reference.HostGraph(alive.shape[0], alive, keys)

        def check_reads(gen_):
            nonlocal reads
            pend = by_gen.pop(gen_, [])
            if not pend:
                return
            lab = host.labels()
            for r, kind, i in pend:
                q = r.req.queries[kind]
                got = r.value[kind][0][i]
                if kind == "same_scc":
                    want = bool(reference.same_scc(
                        host, lab, q[0][i:i + 1], q[1][i:i + 1])[0])
                else:
                    want = int(reference.community_of(
                        host, lab, q[0][i:i + 1])[0])
                reads += int(got != want)

        check_reads(gen0)
        for r in ups:
            want = host.apply(*r.req.ops)
            acks += int(np.sum(want != r.value))
            check_reads(r.gen)
        # answers stamped with a generation no commit produced
        stale += sum(len(v) for v in by_gen.values())
        for k, v in reference.state_gaps(host, *finals[g]).items():
            state_gap[k] += v
        if reopened is not None:
            for k, v in reference.state_gaps(
                    host, *reopened["states"][g]).items():
                durable[f"recovered_{k}"] += v
            durable["recovered_gen_differ"] += abs(
                reopened["gens"][g] - (gens[-1] if gens else gen0))
            # the k-th acknowledged chunk waits for k WAL fsyncs
            synced = np.sort(reopened["wal_fsyncs"])
            for k, r in enumerate(ups, 1):
                durable["acks_before_fsync"] += int(
                    np.searchsorted(synced, r.t_done, "right") < k)
    out = [("acks_differ", acks, 0), ("answers_differ", reads, 0),
           ("answers_at_unknown_gen", stale, 0),
           ("update_gens_repeated", dup, 0),
           *[(k, v, 0) for k, v in state_gap.items()],
           ("requests_failed", failed, 0)]
    if reopened is not None:
        out[-1:-1] = [(k, v, 0) for k, v in durable.items()]
    return out


# -------------------------------------------------------------- metrics ---


def percentile_ms(lat_s: list, weights: list, q: float):
    if not lat_s:
        return None
    return float(np.percentile(np.repeat(lat_s, weights), q)) * 1e3


def end_to_end(records: list, t0: float, t1: float, setup_s: float,
               peak_bytes) -> dict:
    """The client-side numbers of the window, over every request in it."""
    ups = [r for r in records if r.req.kind == "update" and r.error is None]
    reads = [r for r in records if r.req.kind == "read" and r.error is None]

    def lat(r):
        return r.t_done - r.t_submit

    acked = sum(r.req.size for r in ups if r.t_done <= t1)
    out = {"update_ops_s": (acked / (t1 - t0), "ops/s"),
           "update_p95_ms": (percentile_ms([lat(r) for r in ups],
                                           [r.req.size for r in ups], 95),
                             "ms"),
           "read_p95_ms": (percentile_ms([lat(r) for r in reads],
                                         [r.req.size for r in reads], 95),
                           "ms"),
           "setup_s": (setup_s, "s")}
    if peak_bytes is not None:
        out["peak_hbm_mb"] = (peak_bytes / 1e6, "MB")
    return out


class RunData:
    """What a per-layer metric's reader may look at."""

    def __init__(self, cell, config, traffic, trace, counters_before,
                 counters_after, records, device_kind):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.trace = trace
        self.before, self.after = counters_before, counters_after
        self.records = records
        self.device_kind = device_kind

    def delta(self, key):
        a, b = self.after[key], self.before[key]
        if isinstance(a, dict):
            return {k: a[k] - b[k] for k in a}
        return a - b


# ----------------------------------------------------------------- a run ---


def run_cell(resolved: dict, seed: int, seconds: float, trace: bool, *,
             t_process: float, devices=None, trace_dir: str | None = None
             ) -> dict:
    """One run of one cell; returns the result object (not yet printed).
    ``devices`` is None off the accelerator (the tests), where nothing is
    read from a device."""
    import contextlib

    import jax

    from bench import stacks, workload

    cell, config = resolved["cell"], resolved["config"]
    traffic = resolved["traffic"]
    compiles = CompileCounter()
    workdir = tempfile.mkdtemp(prefix="bench-")
    stack = None
    try:
        stack = stacks.load(config["stack"])(config, seed, workdir,
                                             traffic["sessions"])
        pools = [workload.session_pool(traffic, stack.graphs, seed, s)
                 for s in range(traffic["sessions"])]
        warm_pool = workload.session_pool(
            dict(traffic, pool=64), stack.graphs, seed, traffic["sessions"])
        warm_records = []

        def record(req, value, gen):
            now = time.perf_counter()
            warm_records.append(Record(req, now, now, value, gen))

        def warm_issue(s, req):
            value, gen = issue(stack.client(s, req.graph), req)
            record(req, value, gen)

        stack.warm(warm_issue, warm_pool, record)
        before = stack.counters()
        n_compiles = compiles.n
        annotate = jax.profiler.TraceAnnotation if trace else (
            lambda name: contextlib.nullcontext())
        if trace:
            trace_dir = trace_dir or os.path.join(workdir, "trace")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t_setup_end = time.perf_counter()
        t0, t1, records, hung = drive(stack, traffic, pools, seconds,
                                      annotate)
        t_stop = time.perf_counter()
        if trace:
            jax.profiler.stop_trace()
        in_window = compiles.n - n_compiles
        after = stack.counters()
        peak = None
        if devices is not None:
            peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in devices)
        finals, boot = stack.final_states(), stack.boot
        closed, stack = stack, None
        closed.close()
        t_reopen = time.perf_counter()
        reopened = closed.reopen()
        if reopened is not None:
            log(f"cold reopen of the store: "
                f"{time.perf_counter() - t_reopen:.3f} s")
        log(f"compiles in window: {in_window}")
        log(f"window: {len(records)} requests, "
            f"{sum(r.req.size for r in records if r.req.kind == 'update')}"
            f" update ops, {hung} sessions still busy at the join")
        checks = check(boot, warm_records + records, finals, reopened)
        if hung:
            checks.append(("sessions_hung", hung, 0))
        correct = all(v <= lim for _, v, lim in checks)
        result = {"correct": correct, "attempted": len(records),
                  "failed": sum(1 for r in records if r.error is not None)}
        kind = devices[0].device_kind if devices else "none"
        if not trace:
            e2e = end_to_end(records, t0, t1, t_setup_end - t_process,
                             peak)
            metrics = {}
            for m in resolved["end_to_end"]:
                val = e2e.get(m["name"])
                if val is not None and val[0] is not None:
                    metrics[m["name"]] = {"value": val[0], "unit": m["unit"]}
        else:
            from bench import trace as trace_mod
            summary = trace_mod.summarize(trace_dir, t_setup_end, t_stop)
            data = RunData(cell, config, traffic, summary, before, after,
                           records, kind)
            metrics = {}
            for m in resolved["per_layer"]:
                val = metric_reader(m["name"])(data)
                if val is not None:
                    metrics[m["name"]] = {"value": val, "unit": m["unit"]}
            result["breakdown"] = summary.breakdown()
        result["metrics"] = metrics
        dev = {"platform": devices[0].platform if devices else "cpu",
               "kind": kind, "count": len(devices) if devices else 0,
               "memory_peak_bytes": peak}
        if trace:
            dev["busy_s"] = summary.busy_s
            dev["window_s"] = summary.window_s
        result["device"] = dev
        result["checks"] = {name: {"value": v, "limit": lim}
                            for name, v, lim in checks}
        result["_compiles_in_window"] = in_window
        return result
    finally:
        if stack is not None:
            stack.close()
        shutil.rmtree(workdir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit("bench: the system under test (src/repro) is not in this "
                 "checkout; nothing was run")
    resolved = resolve(load_spec(), args.workload)
    devices = devices_or_exit(resolved["cell"]["chips"])
    enable_compile_cache()
    sys.path.insert(0, str(ROOT / "src"))
    result = run_cell(resolved, args.seed, args.seconds, bool(args.trace),
                      t_process=T_PROCESS, devices=devices)
    result.pop("_compiles_in_window")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
