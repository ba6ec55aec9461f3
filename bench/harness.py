"""Find a cell's parts by name and print its result.

Everything a cell needs is found by the names in ``BENCHMARK.json``:

* the configuration: the entry's ``file`` (``bench/configs/<config>.json``),
  whose ``kind`` names the cell body ``bench/kinds/<kind>.py`` and whose plain
  reference is ``bench/configs/<config>_reference.py``;
* the traffic mix: ``bench/traffic/<traffic>.json``, parameters read by the
  kind's one general generator;
* a per-layer metric: ``bench/metrics/<metric>.py``, or, for a name with a
  ``.suffix`` that tells cells apart, ``bench/metrics/<name before the
  first dot>.py``;
* a kernel's work count: ``bench/work/<kernel>.py``;
* the chip's peaks: ``bench/peaks.json``, keyed by ``device_kind``.

Adding a configuration, a mix or a metric therefore adds files and entries
and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(path, name):
    """Import the file at ``path`` as a module called ``name``."""
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Benchmark:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root=ROOT):
        self.root = root
        self.bench_dir = os.path.join(root, "bench")
        self.spec = load_json(os.path.join(root, "BENCHMARK.json"))

    # ------------------------------------------------------------ lookups
    def cell(self, name):
        for cell in self.spec["workloads"]:
            if cell["name"] == name:
                return cell
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config_entry(self, name):
        for cfg in self.spec["configs"]:
            if cfg["name"] == name:
                return cfg
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def config(self, name):
        """The configuration file's contents, with its name."""
        cfg = dict(load_json(os.path.join(self.root,
                                          self.config_entry(name)["file"])))
        cfg["name"] = name
        return cfg

    def traffic(self, name):
        mix = dict(load_json(os.path.join(self.bench_dir, "traffic",
                                          f"{name}.json")))
        mix["name"] = name
        return mix

    def kind(self, kind):
        return load_module(os.path.join(self.bench_dir, "kinds", f"{kind}.py"),
                           f"bench_kind_{kind}")

    def reference(self, config_name):
        return load_module(
            os.path.join(self.bench_dir, "configs",
                         f"{config_name}_reference.py"),
            f"bench_ref_{config_name.replace('-', '_').replace('.', '_')}")

    def metric_reader(self, name):
        for stem in (name, name.split(".")[0]):
            path = os.path.join(self.bench_dir, "metrics", f"{stem}.py")
            if os.path.isfile(path):
                return load_module(path, "bench_metric_" + stem.replace(".", "_"))
        raise FileNotFoundError(f"no reader for metric {name!r}")

    def work(self, kernel):
        return load_module(os.path.join(self.bench_dir, "work", f"{kernel}.py"),
                           f"bench_work_{kernel}")

    def peaks(self, device_kind):
        table = load_json(os.path.join(self.bench_dir, "peaks.json"))
        if device_kind not in table:
            raise KeyError(f"device kind {device_kind!r} is not in "
                           f"bench/peaks.json; add its published peaks")
        return table[device_kind]

    # ------------------------------------------------ metrics of one cell
    def end_to_end(self, cell_name):
        return [m for m in self.spec["end_to_end"]
                if cell_name in m.get("workloads", [cell_name])]

    def per_layer(self, cell_name):
        moved = {m["name"] for m in self.end_to_end(cell_name)}
        return [m for m in self.spec["per_layer"]
                if cell_name in m.get("workloads", [cell_name])
                and m["moves"] in moved]


def prepare(root=ROOT):
    """Make the program under test importable and turn on its compile
    cache (``repro.utils.compile_cache``: ``<checkout>/.jax_cache``, a fixed
    path that later runs in the checkout find, unless
    ``JAX_COMPILATION_CACHE_DIR`` names another).  Call before JAX is
    first used."""
    for path in (os.path.join(root, "src"), root):
        if path not in sys.path:
            sys.path.insert(0, path)
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()


def correct(checks):
    """Whether every compared number is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def require_chips(n):
    """The TPU devices a cell runs on; exits non-zero where there are none,
    or fewer than ``n``.  Never falls back to the CPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        sys.exit(f"bench: no accelerator ({e})")
    if devices[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX platform is "
                 f"{devices[0].platform!r}); the benchmark runs only on a TPU")
    if len(devices) < n:
        sys.exit(f"bench: the cell needs {n} chips, JAX sees {len(devices)}")
    return devices[:n]


def device_record(devices):
    """platform, device_kind, count and the fullest chip's peak memory."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


class CompileCounter:
    """Counts the XLA compiles JAX reports while ``active`` (there should
    be none inside a measured window)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.active and event == self.EVENT:
            self.count += 1


def log(msg):
    """A line for the run's log on standard error."""
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def emit(result, checks):
    """Print the compared numbers beside their limits, last on standard
    error, then the result as the last line of standard output, with the
    checks as its last key."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    out = dict(result)
    out["checks"] = checks
    print(json.dumps(out), flush=True)
