"""What every cell shares: finding a cell's files by name, the device
checks, compile accounting, the profiler window, the per-layer readers and
the result line."""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
OUT = ROOT / ".bench_out"  # traces of the current run; listed in .gitignore

# how long a sampling cell's --trace 1 run profiles: some tens of sampler
# steps, short enough to read back in seconds.  A serving cell profiles its
# whole window, which its check needs to finish requests in
TRACE_SECONDS = 8.0


class CellError(RuntimeError):
    pass


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The cell ``name`` with its configuration, traffic mix and metrics."""
    bench = bench or load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise CellError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cfg = json.loads((ROOT / config["file"]).read_text())
    # a file that names no module fails here, before any device work
    arch(cfg)
    reference(cfg)
    mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    per_layer = [m for m in bench["per_layer"] if name in m.get("workloads", [name])]
    return {"name": name, "chips": int(cell["chips"]), "cfg": cfg, "mix": mix,
            "end_to_end": e2e, "per_layer": per_layer}


def _cell_module(package: str, cfg: dict, key: str):
    """The module ``<package>.<cfg[key]>``: ``bench/<package>/<cfg[key]>.py``
    (or a module already registered under that name in ``sys.modules``)."""
    name = cfg.get(key)
    if not name:
        raise CellError(f"the configuration file gives no {key!r}")
    try:
        return importlib.import_module(f"{package}.{name}")
    except ModuleNotFoundError as e:
        if e.name not in (package, f"{package}.{name}"):
            raise
        path = (BENCH / package / f"{name}.py").relative_to(ROOT)
        raise CellError(f"{key} {name!r} names no module: {path} is missing") from None


def arch(cfg: dict):
    """The architecture module ``bench/arch/<model_type>.py`` that the
    configuration file names by its ``model_type``.  It provides:

    * ``model_config(cfg)``: the program's ``ModelConfig`` for the file;
    * ``shapes(cfg)``: the weights' nested dict of leaf shapes, the layout
      the program loads;
    * ``NORM_GAINS``: the names of the leaves that are norm gains, drawn
      as ``1 + weights.GAIN_STD * N(0, 1)``;
    * ``std(path, cfg)``: the init scale of any other leaf;
    * ``matmul_params(cfg)``: the weights a token passes through in a
      matmul, the vocabulary head's ``hidden_size x vocab_size`` included;
    * ``attention_flops(cfg, context)``: FLOPs of one query token
      attending ``context`` positions, over all layers;
    * ``kv_bytes_per_token(cfg)``: one position's KV cache bytes over all
      layers, for one member."""
    return _cell_module("arch", cfg, "model_type")


def reference(cfg: dict):
    """The plain reference ``bench/reference/<reference>.py`` that the
    configuration file names by its ``reference`` key.  It imports nothing
    of the program and provides, each ``precision`` being ``"f32"``
    (float32, every matmul at ``highest``) or ``"fp8"`` (the control: matmul
    operands rounded to float8 e4m3 under a per-tensor scale):

    * ``hidden(cfg, params, tokens, precision="f32", remat=False)``: the
      final-normed hidden states (S, D) of one sequence of tokens (S,);
    * ``logits(cfg, params, h, precision="f32")``: vocabulary logits
      (S, V) of hidden states (S, D);
    * ``mixture_logprobs(member_logits)``: (K, ..., V) member logits to the
      log of the mean of the members' next-token distributions;
    * ``potential_and_grad(cfg, params, batch, *, n_data, weight_decay,
      precision="f32", rows=None)``: ``(U, nll_per_token, dU/dparams)`` for
      U = n_data / tokens * NLL + weight_decay * ||params||^2 over a batch
      of ``tokens``/``labels`` (B, S), its first ``rows`` rows where given.

    ``params`` is one weight set in the layout of the architecture's
    ``shapes``."""
    return _cell_module("reference", cfg, "reference")


def peak(device_kind: str) -> dict:
    peaks = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in peaks:
        raise CellError(f"device kind {device_kind!r} is not in bench/peaks.json")
    return peaks[device_kind]


def seed_key(seed: int, stream: int):
    """A typed JAX key for one stream of a run's seed (seeds may exceed 32
    bits)."""
    import jax
    import jax.numpy as jnp

    key = jax.random.fold_in(jax.random.key(stream), jnp.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, jnp.uint32((seed >> 32) & 0xFFFFFFFF))


def enable_compile_cache() -> str:
    """The program's fixed cache directory (or JAX_COMPILATION_CACHE_DIR),
    with every program cached, so a second run of a cell compiles nothing."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache as place

    path = place()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Seconds JAX spent compiling or loading programs (the backend compile
    event wraps both), programs compiled, and persistent-cache hits.
    ``mark()`` starts a count of what happens after it (the window)."""

    def __init__(self):
        import jax

        self.seconds, self.programs, self.cache_hits = 0.0, 0, 0
        self._mark = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> None:
        self._mark = self.programs

    @property
    def since_mark(self) -> int:
        return self.programs - self._mark

    def __str__(self):
        return (f"{self.seconds:.3f} s compiling or loading {self.programs} programs "
                f"({self.cache_hits} persistent-cache hits)")


def note(msg: str) -> None:
    """A line of the run's log (standard error)."""
    print(msg, file=sys.stderr, flush=True)


def percentile(values, q: float) -> float:
    """The q-th percentile, linear between order statistics."""
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


def device_info(devices) -> dict:
    peak_bytes = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak_bytes = max(peak_bytes, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak_bytes}


class Profile:
    """A jax.profiler window over the work inside ``with``; ``reduce()``
    gives the trace reduction (``trace_reduce.reduce``)."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.dir = OUT / "trace"

    def __enter__(self):
        if self.enabled:
            import jax

            shutil.rmtree(self.dir, ignore_errors=True)
            self.dir.mkdir(parents=True)
            jax.profiler.start_trace(str(self.dir))
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax

            jax.profiler.stop_trace()
        return False

    def reduce(self, num_devices: int) -> dict:
        import trace_reduce

        files = sorted(self.dir.rglob("*.xplane.pb"))
        if not files:
            raise CellError("the profiler wrote no trace")
        out = trace_reduce.reduce(str(files[-1]), num_devices=num_devices)
        shutil.rmtree(self.dir, ignore_errors=True)
        return out


def read_per_layer(metrics: list, ctx: dict) -> dict:
    """Run each per-layer metric's reader (``metrics/<name>.py``); a reader
    that finds nothing to read returns None and its metric is left out."""
    out = {}
    for m in metrics:
        path = BENCH / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(f"bench_metric_{len(out)}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        value = mod.read(ctx)
        if value is not None:
            if not math.isfinite(value):
                raise CellError(f"metric {m['name']} read {value}")
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def checks_line(checks: list) -> dict:
    """The compared numbers, each beside its limit: [(name, value, limit)]."""
    return {name: {"value": value, "limit": limit} for name, value, limit in checks}


def finish(result: dict, checks: list) -> dict:
    """Print each compared number beside its limit as the last lines of
    standard error, and put them last in the result."""
    for name, value, limit in checks:
        note(f"check {name}: {value!r} limit {limit!r} {'ok' if value <= limit else 'FAIL'}")
    result["checks"] = checks_line(checks)
    return result


def elapsed(t0: float) -> float:
    return time.perf_counter() - t0

