"""One run of one cell: set-up, the measured window, the check, the result.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Everything else is
found by name: the configuration in ``configs/<config>.json`` (its
``family`` names a module in ``families/``), the traffic in
``traffic/<traffic>.json``, each metric's reader in ``metrics/<name>.py``.

The window drives ``Gateway(backend="runtime", policy="sage",
time_scale=0)`` with one node per chip and the Gateway's other defaults,
through ``register`` and ``invoke_async(...).wait()``. Set-up draws the
weights, registers every function, and warms every function's context on
every node, so that nothing compiles in the window.
"""
from __future__ import annotations

import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Dict, List, Optional

import numpy as np

import reference
import schedules
import trace_reduce
import weights
from stats import percentile

HERE = Path(__file__).resolve().parent
WAIT_PAST_CLOSE_S = 60.0  # an answer later than this never came


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    bench: dict

    @functools.cached_property
    def family(self):
        return load_module(HERE / "families" / f"{self.config['family']}.py")

    def metrics(self, trace: bool) -> List[dict]:
        """The entries of the metrics this cell reports: end-to-end ones
        untraced, per-layer ones traced."""
        e2e = [m for m in self.bench["end_to_end"]
               if self.name in m.get("workloads", [self.name])]
        if not trace:
            return e2e
        mine = {m["name"] for m in e2e}
        return [m for m in self.bench["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


def load_cell(root: Path, workload: str, *, config_file: Optional[Path] = None,
              traffic_file: Optional[Path] = None) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    entry = [w for w in bench["workloads"] if w["name"] == workload]
    if not entry:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    (entry,) = entry
    return Cell(
        name=workload, chips=entry["chips"], bench=bench,
        config=load_json(config_file or HERE / "configs" / f"{entry['config']}.json"),
        traffic=load_json(traffic_file or HERE / "traffic" / f"{entry['traffic']}.json"))


@dataclass
class Inv:
    """One invocation the benchmark sent, as the client saw it."""
    function: int
    seed: int
    due: float           # host clock, seconds
    sent: float = math.nan
    done: float = math.nan
    record: Any = None   # the program's InvocationRecord
    error: Optional[str] = None
    tier: Optional[str] = None  # the function's residency when dispatched

    @property
    def ok(self) -> bool:
        return (self.error is None and self.record is not None
                and self.record.error is None)

    @property
    def latency_ms(self) -> float:
        return (self.done - self.due) * 1e3 if self.ok else math.inf

    @property
    def cold(self) -> bool:
        return self.tier != "device"


@dataclass
class RunData:
    """What the metric readers read."""
    cell: Cell
    seconds: float
    setup_s: float
    t_open: float
    invs: List[Inv]
    counters: Dict[str, int]      # daemon counters over the window, all nodes
    counts: dict                  # FLOPs and bytes of one forward
    peaks: Optional[dict]         # the chip's peaks (None off a known chip)
    trace: Optional[dict] = None  # trace_reduce.reduce() of the traced span

    @property
    def t_close(self) -> float:
        return self.t_open + self.seconds

    @property
    def served(self) -> List[Inv]:
        return [i for i in self.invs if i.ok]


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX found {info['platform']!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return info


def _peak_bytes(chips: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


def _counters(nodes) -> Dict[str, int]:
    keys = ("loads", "host_promotions", "evictions", "shared_hits",
            "bytes_loaded", "load_failures")
    return {k: sum(int(n.daemon.stats.get(k, 0)) for n in nodes) for k in keys}


class Run:
    """Drives one run; ``result()`` returns the result line's object."""

    def __init__(self, cell: Cell, *, seed: int, seconds: float, trace: bool,
                 t_start: float, require_tpu: bool = True, control: bool = False,
                 log=lambda s: print(s, file=sys.stderr, flush=True)):
        self.cell, self.seed, self.seconds = cell, seed, seconds
        self.trace, self.t_start, self.control = trace, t_start, control
        self.require_tpu, self.log = require_tpu, log
        self.tr = cell.traffic
        self.names = [f"f{i:02d}" for i in range(self.tr["functions"])]

    # ---------------------------------------------------------------- set-up
    def _setup(self):
        import jax

        from repro.api import FunctionSpec, Gateway
        from repro.core.functions import model_config
        from repro.models import init_params

        cfg, fam = self.cell.config, self.cell.family
        mc = model_config(cfg["program_arch"], cfg["full_width"])
        fam.check_program(cfg, mc)
        shapes = fam.param_shapes(cfg)
        weights.check_layout(shapes, jax.eval_shape(
            lambda: init_params(mc, jax.random.PRNGKey(0))))
        t = time.perf_counter()
        self.trees = weights.make(shapes, fam.draw, fam.DISTINCT_LEAF,
                                  self.seed, len(self.names))
        self.log(f"weights: {len(self.trees)} function(s) in "
                 f"{time.perf_counter() - t:.3f}s")

        @dataclass(frozen=True)
        class BenchSpec(FunctionSpec):
            # the benchmark's weights, drawn on the device from the seed
            weights_tree: Any = field(default=None, compare=False, repr=False)

            def host_params(self):
                return self.weights_tree

        self.gw = Gateway(backend="runtime", policy="sage", time_scale=0.0,
                          n_nodes=self.cell.chips,
                          seed=schedules.derive_seed(self.seed, 5))
        self.nodes = list(getattr(self.gw.runtime, "nodes", [self.gw.runtime]))
        t = time.perf_counter()
        for name, tree in zip(self.names, self.trees):
            with _annotate("cb.register"):
                self.gw.register(BenchSpec(
                    name=name, arch=cfg["program_arch"],
                    full_width=cfg["full_width"], batch=self.tr["batch"],
                    seq=self.tr["seq"], weights_tree=tree))
        self.log(f"register: {time.perf_counter() - t:.3f}s")

    def _warm(self):
        """Every function's context on every node, least popular first,
        so that the popular ones are resident when the window opens; then
        ``warmup_seconds`` of the cell's own traffic."""
        from repro.core.functions import make_request

        t = time.perf_counter()
        base = schedules.derive_seed(self.seed, 7)
        errors: List[Exception] = []

        def warm_node(i, node):
            try:
                for j, name in reversed(list(enumerate(self.names))):
                    req = make_request(node.db, node.engines[name].fn,
                                       batch=self.tr["batch"], seq=self.tr["seq"],
                                       seed=schedules.derive_seed(base, i, j))
                    node.submit(req).result(timeout=600)
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=warm_node, args=(i, n))
                   for i, n in enumerate(self.nodes)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=1200)
        if errors or any(th.is_alive() for th in threads):
            raise RuntimeError(f"warm-up failed: {errors!r}")
        if self.tr["warmup_seconds"]:
            invs = self._drive(base, self.tr["warmup_seconds"], time.perf_counter())
            bad = [i for i in invs if not i.ok]
            if bad:
                raise RuntimeError(f"warm-up traffic failed: {bad[0]!r}")
        self.log(f"warm-up: {time.perf_counter() - t:.3f}s")

    # ---------------------------------------------------------------- window
    def _invoke(self, inv: Inv, timeout: float) -> None:
        name = self.names[inv.function]
        if len(self.nodes) == 1:
            inv.tier = self.nodes[0].daemon.residency(name)[0]
        inv.sent = time.perf_counter()
        try:
            with _annotate("cb.submit"):
                h = self.gw.invoke_async(name, seed=inv.seed)
            with _annotate("cb.wait"):
                inv.record = h.wait(timeout, strict=False)
        except Exception as e:  # noqa: BLE001 — an answer that never came
            inv.error = f"{type(e).__name__}: {e}"
        inv.done = time.perf_counter()
        if inv.record is not None and inv.record.dispatch_tier is not None:
            inv.tier = inv.record.dispatch_tier

    def _drive(self, seed: int, seconds: float, t_open: float) -> List[Inv]:
        if self.tr["loop"] == "closed":
            return self._closed(seed, seconds, t_open)
        return self._open(seed, seconds, t_open)

    def _closed(self, seed, seconds, t_open) -> List[Inv]:
        out: List[Inv] = []
        lock = threading.Lock()
        t_close = t_open + seconds

        def caller(c):
            k = 0
            while time.perf_counter() < t_close:
                inv = Inv(function=0, due=time.perf_counter(),
                          seed=schedules.closed_loop_seed(seed, c, k))
                k += 1
                self._invoke(inv, t_close + WAIT_PAST_CLOSE_S - inv.due)
                with lock:
                    out.append(inv)

        threads = [threading.Thread(target=caller, args=(c,))
                   for c in range(self.tr["callers"])]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=seconds + 2 * WAIT_PAST_CLOSE_S)
        if any(th.is_alive() for th in threads):
            raise RuntimeError("a caller did not return")
        return out

    def _open(self, seed, seconds, t_open) -> List[Inv]:
        sched = schedules.open_loop(seed, seconds=seconds,
                                    rate_per_s=self.tr["rate_per_s"],
                                    functions=len(self.names),
                                    zipf_s=self.tr["zipf_s"])
        invs = [Inv(function=a.function, seed=a.seed, due=t_open + a.t)
                for a in sched]
        deadline = t_open + seconds + WAIT_PAST_CLOSE_S
        threads = []
        for inv in invs:
            lag = inv.due - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            th = threading.Thread(
                target=self._invoke,
                args=(inv, max(deadline - time.perf_counter(), 1e-3)))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=max(deadline - time.perf_counter(), 0) + 30)
        if any(th.is_alive() for th in threads):
            raise RuntimeError("an invocation's waiter did not return")
        return invs

    def _window(self):
        """The measured window, the first ``trace_seconds`` of it traced."""
        import jax

        out: Dict[str, Any] = {}

        def drive(t_open):
            out["invs"] = self._drive(self.seed, self.seconds, t_open)

        before = _counters(self.nodes)
        tdir = None
        if self.trace:
            tdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        try:
            with _annotate(trace_reduce.WINDOW_SPAN):
                self.t_open = time.perf_counter()
                worker = threading.Thread(target=drive, args=(self.t_open,))
                worker.start()
                if self.trace:
                    time.sleep(min(self.tr["trace_seconds"], self.seconds))
            if self.trace:
                jax.profiler.stop_trace()
            worker.join(timeout=self.seconds + 3 * WAIT_PAST_CLOSE_S)
            if worker.is_alive() or "invs" not in out:
                raise RuntimeError("the window's driver did not finish")
            self.invs = out["invs"]
            after = _counters(self.nodes)
            self.counters = {k: after[k] - before[k] for k in after}
            self.reduced = None
            if self.trace:
                (pb,) = list(Path(tdir).rglob("*.xplane.pb"))
                self.reduced = trace_reduce.reduce(trace_reduce.load(str(pb)),
                                                    chips=self.cell.chips)
        finally:
            if tdir is not None:
                shutil.rmtree(tdir, ignore_errors=True)

    # ----------------------------------------------------------------- check
    def _sample(self) -> List[Inv]:
        """The invocations checked: one of each node's, one cold one where
        there is one, the rest at random; all drawn from the seed."""
        served = [i for i in self.invs if i.ok]
        rng = np.random.default_rng(schedules.derive_seed(self.seed, 6))
        order = [served[k] for k in rng.permutation(len(served))]
        want = self.tr["check_sample"]
        pick: List[Inv] = []
        for node in self.nodes:
            mine = [i for i in order if i.record.node_id == node.node_id]
            pick += mine[:1]
        pick += [i for i in order if i.cold][:1]
        for i in order:
            if len(pick) >= want:
                break
            if all(i is not p for p in pick):
                pick.append(i)
        return pick

    def _check(self) -> dict:
        import jax

        sample = self._sample()
        by_id = {n.node_id: n for n in self.nodes}
        got = [np.asarray(by_id[i.record.node_id].db.fetch(i.record.result))
               for i in sample]
        self.memory_peak = _peak_bytes(self.cell.chips)
        self.gw.shutdown()
        del self.gw, self.nodes, by_id
        gc.collect()

        tr, fam = self.tr, self.cell.family
        shape = (tr["batch"], tr["returned_logits"])
        tokens = [np.random.default_rng(i.seed).integers(
            0, tr["token_ids_below"], (tr["batch"], tr["seq"]), dtype=np.int32)
            for i in sample]
        fn_of = [i.function for i in sample]
        dev = jax.devices()[0]
        t = time.perf_counter()
        rows = reference.last_rows(fam, self.cell.config, self.trees, tokens,
                                   fn_of, control=False,
                                   block=tr["check_block"], device=dev)
        self.log(f"reference: {len(sample)} invocations in "
                 f"{time.perf_counter() - t:.3f}s")
        # batch 1: the row of the request's only sequence
        shapes_ok = all(g.shape == shape for g in got)
        err = (reference.logit_err([g[0] for g in got], rows)
               if shapes_ok and sample else math.inf)
        failed = sum(not i.ok for i in self.invs)
        checks = {
            "failed": {"value": failed, "limit": 0},
            "checked": {"value": len(sample), "limit": tr["check_sample"]},
            "logit_err": {"value": err,
                          "limit": self.cell.config["logit_err_limit"]},
        }
        correct = (failed == 0 and shapes_ok
                   and len(sample) >= tr["check_sample"]
                   and err <= self.cell.config["logit_err_limit"])
        if self.control:
            crow = reference.last_rows(fam, self.cell.config, self.trees,
                                       tokens, fn_of, control=True,
                                       block=tr["check_block"], device=dev)
            checks["control_logit_err"] = {
                "value": reference.logit_err(
                    [c[:tr["returned_logits"]] for c in crow], rows),
                "limit": self.cell.config["logit_err_limit"]}
        return {"correct": bool(correct), "checks": checks}

    # ---------------------------------------------------------------- result
    def result(self) -> dict:
        info = device_info(self.cell.chips, self.require_tpu)
        print(f"device: {info['platform']} {info['kind']} x{info['count']}",
              flush=True)
        self._setup()
        self._warm()
        self._window()
        setup_s = self.t_open - self.t_start
        lag = [(i.sent - i.due) * 1e3 for i in self.invs if not math.isnan(i.sent)]
        self.log(f"window: {len(self.invs)} invocations, counters {self.counters}, "
                 f"generator lag p50 {percentile(lag, 50):.3f}ms "
                 f"max {max(lag):.3f}ms")
        peaks_table = load_json(HERE / "peaks.json")["devices"]
        cfg, tr = self.cell.config, self.tr
        run = RunData(
            cell=self.cell, seconds=self.seconds, setup_s=setup_s,
            t_open=self.t_open, invs=self.invs, counters=self.counters,
            counts=self.cell.family.counts(cfg, tr["batch"], tr["seq"]),
            peaks=peaks_table.get(info["kind"]), trace=self.reduced)
        if self.require_tpu and run.peaks is None:
            raise KeyError(f"no peaks for device kind {info['kind']!r} in peaks.json")
        metrics = {}
        for m in self.cell.metrics(self.trace):
            value = load_module(HERE / "metrics" / f"{m['name']}.py").read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        check = self._check()
        device = {**info, "memory_peak_bytes": self.memory_peak}
        out: Dict[str, Any] = {
            "correct": check["correct"], "attempted": len(self.invs),
            "failed": check["checks"]["failed"]["value"],
            "metrics": metrics, "device": device}
        if self.trace:
            device["busy_s"] = self.reduced["busy_s"]
            device["window_s"] = self.reduced["window_s"]
            out["breakdown"] = trace_reduce.breakdown(self.reduced)
        out["generator_lag_ms"] = {"p50": percentile(lag, 50),
                                   "p95": percentile(lag, 95), "max": max(lag)}
        out["checks"] = check["checks"]
        return out
