"""What every cell's run shares: the manifest and the files it names,
the device check, the compile cache, JAX's own account of compiles,
and the result line.  Nothing here knows a cell by name."""

from __future__ import annotations

import copy
import importlib.util
import json
import os
import sys
from typing import Any, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NO_ACCELERATOR = 3      # exit code: no TPU, or fewer chips than asked


def read_json(*parts: str) -> Dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _tiny(obj: Dict) -> Dict:
    """The CPU rehearsal's sizes: a file's `tiny` entries laid over it."""
    out = copy.deepcopy(obj)
    out.update(out.pop("tiny", {}))
    return out


class Cell:
    """One entry of BENCHMARK.json's `workloads` with the files it
    names: its own (`workloads/<name>.json`), its configuration's and
    its traffic mix's."""

    def __init__(self, name: str, rehearsal: bool = False,
                 root: str = ROOT):
        self.root = root
        self.manifest = read_json(root, "BENCHMARK.json")
        entry = [w for w in self.manifest["workloads"] if w["name"] == name]
        if not entry:
            raise SystemExit(f"benchmark: no workload {name!r} in "
                             f"BENCHMARK.json")
        self.name, self.entry = name, entry[0]
        self.rehearsal = rehearsal
        bench = os.path.join(root, "benchmark")
        self.spec = read_json(bench, "workloads", name + ".json")
        cfg_entry = [c for c in self.manifest["configs"]
                     if c["name"] == self.entry["config"]][0]
        self.config = read_json(root, cfg_entry["file"])
        self.traffic = read_json(bench, "traffic",
                                 self.entry["traffic"] + ".json")
        if rehearsal:
            self.config, self.traffic = _tiny(self.config), _tiny(self.traffic)
        self.chips = int(self.entry["chips"])
        self.peaks_table = read_json(bench, "peaks.json")

    def metrics(self, section: str) -> List[Dict]:
        """The `end_to_end` or `per_layer` metrics this cell reports: a
        metric without a `workloads` key belongs to every cell that
        reports the metric it moves (every cell, for an end-to-end
        metric)."""
        mine = {m["name"] for m in self.manifest["end_to_end"]
                if self.name in m.get("workloads", [self.name])}
        if section == "end_to_end":
            return [m for m in self.manifest["end_to_end"]
                    if m["name"] in mine]
        return [m for m in self.manifest["per_layer"]
                if (self.name in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]

    def load(self, kind: str, name: str):
        """A module of the benchmark found by name: `runners/<name>.py`,
        `traffic/<name>.py`, `layer_metrics/<name>.py` (a name may hold
        dots, so by path and not by import)."""
        path = os.path.join(self.root, "benchmark", kind, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark.{kind}.{name.replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod


# -- JAX: platform, cache, compile account ------------------------------------

class CompileLog:
    """JAX's own account of this process's compiles and of its
    persistent cache, from `jax.monitoring`."""

    def __init__(self):
        import jax
        self.backend_compile_s = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, seconds, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compile_s += seconds
            self.compiles += 1

    def _ev(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"backend_compile_s": self.backend_compile_s,
                "compiles": self.compiles, "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


def start_jax(cell: Cell) -> CompileLog:
    """Place the compile cache, check the device, return the compile
    log.  Exits with NO_ACCELERATOR (and prints no result) unless JAX's
    default backend is a TPU with the chips the cell asks for; the
    rehearsal wants the CPU instead and says so in its device record."""
    if not os.path.isdir(os.path.join(cell.root, "singa_tpu")):
        print("benchmark: the singa_tpu package is not beside benchmark/;"
              " nothing to measure", file=sys.stderr)
        raise SystemExit(2)
    if cell.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        if cell.chips > 1:
            flags = os.environ.get("XLA_FLAGS", "")
            if "xla_force_host_platform_device_count" not in flags:
                os.environ["XLA_FLAGS"] = (
                    f"{flags} --xla_force_host_platform_device_count="
                    f"{cell.chips}").strip()
    sys.path.insert(0, cell.root)
    import jax
    cache_dir = None
    if not cell.rehearsal:           # the rehearsal's tests stay hermetic
        from singa_tpu.utils import compile_cache
        # <checkout>/.jax_cache, or where JAX_COMPILATION_CACHE_DIR says
        cache_dir = compile_cache.enable()
    # every program into the cache, however fast it compiled, and one
    # cache key from any call path (PERF.md, Pallas tracebacks)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_include_full_tracebacks_in_locations", False)
    want = "cpu" if cell.rehearsal else "tpu"
    backend = jax.default_backend()
    if backend != want:
        print(f"benchmark: JAX's default backend is {backend!r}, not "
              f"{want!r}; nothing was measured", file=sys.stderr)
        raise SystemExit(NO_ACCELERATOR)
    if len(jax.devices()) < cell.chips:
        print(f"benchmark: cell {cell.name!r} needs {cell.chips} chips, "
              f"JAX reports {len(jax.devices())}", file=sys.stderr)
        raise SystemExit(NO_ACCELERATOR)
    log = CompileLog()
    log.cache_dir = cache_dir
    return log


def device_record(chips: int) -> Dict[str, Any]:
    """The device as JAX reports it, and the peak bytes on the fullest
    chip used (0 where the backend reports none, as the CPU does)."""
    import jax
    devs = jax.devices()[:chips]
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in devs]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def peaks(cell: Cell) -> Optional[Dict[str, float]]:
    """This device's row of peaks.json; an accelerator that is not in
    the table is an error, the CPU has none."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in cell.peaks_table:
        raise SystemExit(f"benchmark: no peaks on record for device kind "
                         f"{dev.device_kind!r}; add it to "
                         f"benchmark/peaks.json with its source")
    return cell.peaks_table[dev.device_kind]


class Laps:
    """Where set-up's seconds go, one printed line a phase."""

    def __init__(self):
        import time
        self._clock, self._at = time.perf_counter, time.perf_counter()

    def lap(self, name: str) -> None:
        now = self._clock()
        print(f"setup {name}: {now - self._at:.2f} s", flush=True)
        self._at = now


# -- the comparison's printout and the result line -----------------------------

class Compared:
    """Each number the correctness check compared, beside its limit."""

    def __init__(self):
        self.rows: List[Dict[str, Any]] = []

    def add(self, name: str, value: float, limit: float,
            ok: Optional[bool] = None) -> bool:
        ok = bool(value <= limit) if ok is None else bool(ok)
        self.rows.append({"name": name, "value": value, "limit": limit,
                          "ok": ok})
        print(f"compared {name}: {value!r} (limit {limit!r}) "
              f"{'ok' if ok else 'NOT OK'}", flush=True)
        return ok

    @property
    def ok(self) -> bool:
        return bool(self.rows) and all(r["ok"] for r in self.rows)


def _keep_facts(cell: Cell, facts: Dict, metrics: Dict) -> None:
    """What the per-layer readers read, kept beside the trace for the
    reader of a traced run (`.bench_trace/<cell>/facts.json`)."""
    spans: Dict[str, List[float]] = {}
    for row in facts.get("spans", []):
        spans.setdefault(row[0], []).append(row[2] - row[1])
    keep = {"metrics": metrics, "counters": facts.get("counters"),
            "compile": facts.get("compile"),
            "end_to_end": facts.get("end_to_end"),
            "spans": {k: {"count": len(v), "seconds": sum(v)}
                      for k, v in spans.items()},
            "trace": {k: v for k, v in (facts.get("trace") or {}).items()
                      if k != "ops"}}
    path = os.path.join(cell.root, ".bench_trace", cell.name)
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "facts.json"), "w") as f:
        json.dump(keep, f, indent=1)


def result_line(cell: Cell, trace: bool, outcome: Dict) -> str:
    """The last line of standard output.  `outcome` is what a runner
    returned: correct, attempted, failed, end_to_end {name: value},
    facts (for the per-layer readers), and with a trace the device's
    busy_s / window_s and the breakdown."""
    device = device_record(cell.chips)
    if outcome.get("memory_peak_bytes"):
        device["memory_peak_bytes"] = int(outcome["memory_peak_bytes"])
    metrics: Dict[str, Dict] = {}
    extra: Dict[str, Any] = {}
    if cell.rehearsal:
        # a CPU run: counts only, nothing under a device metric's name
        extra["rehearsal"] = True
        extra["counts"] = outcome.get("counts", {})
        if trace:                    # which readers found something
            _keep_facts(cell, outcome["facts"], {})
            extra["readers"] = sorted(
                m["name"] for m in cell.metrics("per_layer")
                if cell.load("layer_metrics", m["name"]).read(
                    outcome["facts"]) is not None)
    elif not trace:
        for m in cell.metrics("end_to_end"):
            metrics[m["name"]] = {"value": outcome["end_to_end"][m["name"]],
                                  "unit": m["unit"]}
    else:
        facts = outcome["facts"]
        for m in cell.metrics("per_layer"):
            value = cell.load("layer_metrics", m["name"]).read(facts)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        tr = facts.get("trace") or {}
        _keep_facts(cell, facts, metrics)
        device["busy_s"] = tr.get("busy_s")
        device["window_s"] = tr.get("window_s")
        if tr.get("breakdown"):
            extra["breakdown"] = tr["breakdown"]
    line = {"correct": bool(outcome["correct"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"]), "metrics": metrics,
            "device": device, **extra}
    return json.dumps(line)
