"""One run of one cell: set up, measure a window, check, report.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); each metric is read by
``bench/metrics/<metric>.py``.  The harness finds them all by name, so a
cell, a mix, a configuration or a metric is added with files and entries.

The window drives ``Engine.step()`` of the engine ``repro.serve.make_engine``
builds, with requests arriving through ``Engine.submit()``.  A
configuration whose ``serving.tp`` is above 1 is served tensor-parallel
over a ``(1, tp)`` mesh of the cell's chips, its weights born in their
shards.  The harness stamps each request's due time itself: in an open
loop a request is timed from when it was due, not from when the loop got
round to it; in a backlog a request is due when it joins the queue.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import traffic as traffic_mod
from bench.peaks import peaks_for
from bench.workcounts import Dims

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"
TRACE_DIR = ROOT / ".bench_cache" / "trace"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CHECK_TOKENS = 256          # served tokens the reference re-reads per run
WARMUP_NEW_TOKENS = 2       # one prefill token and one decode token
RAMP_STEPS_PER_SLOT = 10    # a backlog's ramp gives up after this many


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


# ---------------------------------------------------------------------------
# finding a cell's pieces by name
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]      # metric entries this cell reports
    per_layer: List[Dict]


def load_cell(name: str, spec: Optional[Dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` (or of ``spec``) with its
    configuration, mix and metric entries."""
    spec = spec or load_json(ROOT / "BENCHMARK.json")
    by_name = {w["name"]: w for w in spec["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    config = load_json(ROOT / cfg_entry["file"])
    mix = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in spec["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in moved
                              else [])]
    return Cell(name, int(w["chips"]), config, mix, e2e, layer)


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def model_modules(config: Dict):
    """(adapter, reference) modules of the configuration's ``model``."""
    family = config["model"]
    adapter = importlib.import_module(f"bench.models.{family}_adapter")
    reference = importlib.import_module(f"bench.models.{family}_reference")
    return adapter, reference


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Record:
    """One request as the harness saw it.  Times are ``perf_counter``
    seconds, the clock of the program's own stamps."""
    index: int
    due: float
    submitted: float
    prompt: np.ndarray
    max_new_tokens: int
    request: object = None              # the program's Request
    failed: bool = False

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def placed(self) -> float:
        return self.request.prefill_start_time if self.request else 0.0

    @property
    def token_times(self) -> List[float]:
        return self.request.token_times if self.request else []

    @property
    def generated(self) -> List[int]:
        return list(self.request.generated) if self.request else []


@dataclasses.dataclass
class RunData:
    """Everything a metric reader may read."""
    cell: str
    config: Dict
    dims: Dims
    chips: int
    num_slots: int
    peaks: Dict
    t_open: float
    deadline: float
    t_end: float
    setup_s: float
    records: List[Record]
    phases: Dict[str, Dict[str, float]]
    t_traced: Optional[float] = None    # where the trace begins, if later
    trace: Optional[object] = None      # bench.trace.TraceSummary
    warmup_programs: int = 0            # compiled or loaded in warm-up
    warmup_lengths: int = 0             # distinct prompt lengths warmed

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_open

    def due_in_window(self) -> List[Record]:
        return [r for r in self.records
                if self.t_open <= r.due < self.deadline and not r.failed]

    def ttfts_s(self) -> List[float]:
        """Due time to first token of every request due in the window; a
        request with no first token by the end counts what it waited."""
        return [(r.token_times[0] if r.token_times else self.t_end) - r.due
                for r in self.due_in_window()]

    def queue_waits_s(self) -> List[float]:
        return [(r.placed if r.placed else self.t_end) - r.due
                for r in self.due_in_window()]

    def itls_s(self) -> List[float]:
        out: List[float] = []
        for r in self.records:
            t = [x for x in r.token_times if self.t_open <= x <= self.t_end]
            out.extend(b - a for a, b in zip(t, t[1:]))
        return out

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.records for x in r.token_times
                   if self.t_open <= x <= self.t_end)

    def decode_contexts(self, since: Optional[float] = None) -> List[int]:
        """Keys each decode token of the window (from ``since`` on)
        attended to: token k >= 1 of a request with an n-token prompt
        reads n + k keys (token 0 comes from the prefill)."""
        lo = self.t_open if since is None else since
        out = []
        for r in self.records:
            for k, x in enumerate(r.token_times):
                if k >= 1 and lo <= x <= self.t_end:
                    out.append(r.prompt_len + k)
        return out

    def prefills_in_window(self) -> List[Record]:
        """Requests whose prefill ran in the window (first token in it)."""
        return [r for r in self.records if r.token_times
                and self.t_open <= r.token_times[0] <= self.t_end]


def percentile(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation); None without samples."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class CompileCounter:
    """Backend compilations, counted from JAX's monitoring events."""

    def __init__(self):
        self.count, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration_s: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1
            self.seconds += duration_s

    def close(self) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


def use_compile_cache() -> None:
    """Every program, however small, goes to the persistent cache at a
    fixed path inside the checkout, so a later run compiles only what no
    earlier run there did (the program's page writes for prompt lengths
    new to the checkout)."""
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _engine_config(serving: Dict):
    """The serving settings of a configuration, on the Pallas kernels the
    timed path runs (interpreted off the chip, in tests)."""
    from repro.serve import EngineConfig
    return EngineConfig(num_slots=serving["num_slots"],
                        page_size=serving["page_size"],
                        max_len=serving["max_len"],
                        prefill_chunk=serving["prefill_chunk"],
                        num_pages=serving["num_pages"],
                        prefix_cache=serving["prefix_cache"],
                        kernel_backend="pallas")


def _build_engine(adapter, config: Dict, mcfg, seed: int):
    """The seed's weights and the engine ``make_engine`` builds for them:
    at ``tp`` 1 on one chip; above, over a ``(1, tp)`` mesh, with each
    weight born in its shard."""
    from repro.parallel.mesh import make_host_mesh
    from repro.serve import make_engine
    serving = config["serving"]
    tp = serving["tp"]
    mesh = make_host_mesh(data=1, model=tp) if tp > 1 else None
    params = adapter.make_params(config, mcfg, seed, mesh)
    jax.block_until_ready(params)
    return make_engine(mcfg, params, _engine_config(serving),
                       mesh_shape=(1, tp))


def _fill_slots(engine, top_up, records: List[Record],
                num_slots: int) -> None:
    """Step a backlog, kept topped up, until every slot decodes: each of
    the ``num_slots`` holds a request past its prefill."""
    from repro.serve import RequestState
    for _ in range(RAMP_STEPS_PER_SLOT * num_slots):
        top_up(time.perf_counter())
        running = sum(r.request is not None
                      and r.request.state is RequestState.RUNNING
                      for r in records)
        if running >= num_slots:
            return
        engine.step()
    raise RuntimeError(f"the backlog filled {running} of {num_slots} slots "
                       f"in {RAMP_STEPS_PER_SLOT * num_slots} steps")


def _warm_up(engine, lengths: List[int], seed: int, vocab: int):
    """Prefill every prompt length this run's schedule sends (the padded
    prefill buckets, and the page writes the program compiles for each
    prompt length) and run the decode step: all the shapes the window
    will use, and no others."""
    from repro.serve import GenerateConfig
    rng = np.random.default_rng([seed, 1 << 30])
    for n in lengths:
        engine.submit(rng.integers(0, vocab, size=n, dtype=np.int32),
                      GenerateConfig(max_new_tokens=WARMUP_NEW_TOKENS))
    engine.run()


def _memory_peak(chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def _sample_for_check(records: List[Record], seed: int) -> List[Record]:
    """Finished requests, drawn from the seed, with the longest in it,
    until CHECK_TOKENS served tokens are covered."""
    done = [r for r in records if r.request is not None
            and r.request.finish_reason]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.generated), r.prompt_len))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([seed, 7])
    order = rng.permutation(len(rest))
    sample, tokens = [longest], len(longest.generated)
    for i in order:
        if tokens >= CHECK_TOKENS:
            break
        sample.append(rest[i])
        tokens += len(rest[i].generated)
    return sample


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_process: float, engine_hook=None,
             control: bool = False) -> Dict:
    """Set up, measure, check and reduce one run; returns the result
    object the last line prints.  ``engine_hook(engine)`` lets a test plant
    a fault in the timed path before the window; ``control`` also holds
    the float8 control, in the served tokens' place, to the same check
    (under ``"control"``, which does not decide the run's ``correct``)."""
    from repro.serve import GenerateConfig, RequestState

    config, mix = cell.config, cell.mix
    serving = config["serving"]
    if serving["tp"] != cell.chips:
        raise ValueError(f"{cell.name}: serving.tp {serving['tp']} on "
                         f"{cell.chips} chips; a cell serves one (1, tp) "
                         f"mesh over all its chips")
    dev0 = jax.devices()[0]
    peaks = peaks_for(dev0.device_kind) if dev0.platform == "tpu" else {}
    adapter, reference = model_modules(config)
    compiles = CompileCounter()

    # -- set-up: weights, engine, warm-up -------------------------------
    mcfg = adapter.model_config(config)
    engine = _build_engine(adapter, config, mcfg, seed)
    vocab = config["vocab_size"]
    tr = traffic_mod.Traffic(mix, seed, vocab, seconds)
    if tr.max_tokens > serving["max_len"]:
        raise ValueError(f"mix needs {tr.max_tokens} tokens a request, "
                         f"max_len is {serving['max_len']}")
    n0, s0 = compiles.count, compiles.seconds
    _warm_up(engine, tr.prompt_lengths, seed, vocab)
    warmup_programs = compiles.count - n0
    log(f"warm-up: {len(tr.prompt_lengths)} prompt lengths, "
        f"{warmup_programs} compilations ({compiles.seconds - s0:.3f}s);"
        f" {compiles.count} ({compiles.seconds:.3f}s) in all set-up")
    if engine_hook is not None:
        engine_hook(engine)

    records: List[Record] = []

    def submit(item: traffic_mod.Item, due: float) -> None:
        rec = Record(item.index, due, time.perf_counter(), item.prompt,
                     item.max_new_tokens)
        try:
            rec.request = engine.submit(
                item.prompt, GenerateConfig(max_new_tokens=item.max_new_tokens))
        except ValueError as e:
            rec.failed = True
            log(f"request {item.index} refused: {e}")
        records.append(rec)

    pending = list(tr.items)            # a Poisson schedule, due order
    waiting: List[Record] = []          # a backlog's requests not yet placed

    def top_up(t: float) -> None:
        """Keep a backlog's ``depth`` requests submitted and not yet
        placed; each newcomer is due at ``t``, when it joins the queue."""
        waiting[:] = [r for r in waiting if not r.placed and not r.failed]
        while len(waiting) < tr.depth:
            submit(tr.draw(), t)
            waiting.append(records[-1])

    if tr.depth:
        _fill_slots(engine, top_up, records, serving["num_slots"])
    engine.reset_phases()

    # a configuration whose trace would outlast a run profiles only the
    # window's last ``trace_seconds``: the traced runs report per-layer
    # metrics alone, none of them read from the untraced stretch
    trace_s = min(seconds, config.get("trace_seconds", seconds))

    def start_profiler() -> None:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)

    if trace and trace_s == seconds:
        start_profiler()

    # -- the window --------------------------------------------------------
    n_compiles0 = compiles.count
    t_open = time.perf_counter()
    setup_s = t_open - t_process
    deadline = t_open + seconds
    TA = jax.profiler.TraceAnnotation

    def drive(until: float) -> None:
        while True:
            t = time.perf_counter()
            if t >= until:
                break
            with TA("generator.submit"):
                if tr.depth:
                    top_up(t)
                else:
                    while pending and t_open + pending[0].offset_s <= t:
                        item = pending.pop(0)
                        submit(item, t_open + item.offset_s)
            busy = any(r.request is not None
                       and r.request.state is not RequestState.FINISHED
                       for r in records)
            if busy:
                with TA("engine.step"):
                    engine.step()
            else:
                nxt = t_open + pending[0].offset_s if pending else until
                with TA("generator.wait"):
                    time.sleep(max(0.0, min(nxt, until)
                                   - time.perf_counter()))

    t_traced = t_open
    if trace and trace_s < seconds:
        drive(deadline - trace_s)
        start_profiler()
        t_traced = time.perf_counter()
    with TA("window"):
        drive(deadline)
    t_end = time.perf_counter()
    window_compiles = compiles.count - n_compiles0
    if trace:
        jax.profiler.stop_trace()
        log(f"trace written in {time.perf_counter() - t_end:.3f}s")
    compiles.close()

    late = [r.submitted - r.due for r in records]
    log(f"window {t_end - t_open:.3f}s: {len(records)} of {len(tr.items)} "
        f"requests submitted, generator lateness p50 "
        f"{1e3 * (percentile(late, 50) or 0):.3f} ms"
        f" max {1e3 * max(late, default=0.0):.3f} ms")
    log(f"compilations inside the window: {window_compiles}; "
        f"preemptions {engine._sched.preempt_count}")
    memory_peak = _memory_peak(cell.chips)
    log(f"peak_bytes_in_use {memory_peak}")
    phases = {k: {"wall_s": v.wall_s, "steps": v.steps, "tokens": v.tokens}
              for k, v in engine.phases.items()}
    prompts = [len(it.prompt) for it in tr.items]
    outputs = [it.max_new_tokens for it in tr.items]
    log(f"prompt lengths {traffic_mod.length_stats(prompts)} vs mix "
        f"{mix['prompt_tokens']}")
    log(f"output lengths {traffic_mod.length_stats(outputs)} vs mix "
        f"{mix['output_tokens']}")

    run = RunData(cell=cell.name, config=config, dims=Dims.of(config),
                  chips=cell.chips, num_slots=serving["num_slots"],
                  peaks=peaks, t_open=t_open, deadline=deadline, t_end=t_end,
                  setup_s=setup_s, records=records, phases=phases,
                  t_traced=t_traced,
                  warmup_programs=warmup_programs,
                  warmup_lengths=len(tr.prompt_lengths))

    # -- the check: the program's state is freed first ---------------------
    sample = _sample_for_check(records, seed)
    for r in records:               # keep the stamps, drop the engine
        r.request = _Frozen.of(r.request)
    del engine
    gc.collect()
    checks, control_checks = check_served(reference, config, seed, sample,
                                          control)

    trace_summary = None
    if trace:
        from bench import trace as trace_mod
        t0 = time.perf_counter()
        trace_summary = trace_mod.summarize(trace_mod.load(TRACE_DIR),
                                            cell.chips)
        run.trace = trace_summary
        log(f"trace read in {time.perf_counter() - t0:.3f}s: ops per chip "
            f"{[len(v) for v in trace_summary.ops.values()]}")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = run.setup_s if m["name"] == "setup_s" \
            else metric_reader(m["name"])(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": memory_peak}
    result = {"correct": verdict(checks), "attempted": len(tr.items),
              "failed": sum(r.failed for r in records),
              "metrics": metrics, "device": device}
    if trace_summary is not None:
        device["busy_s"] = trace_summary.busy_s
        device["window_s"] = trace_summary.window_s
        result["breakdown"] = trace_summary.breakdown()
    if control_checks is not None:
        result["control"] = {"correct": verdict(control_checks),
                             "checks": control_checks}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


@dataclasses.dataclass
class _Frozen:
    """The stamps and tokens of a program Request, kept after the engine
    is freed."""
    prefill_start_time: float
    token_times: List[float]
    generated: List[int]
    finish_reason: str

    @classmethod
    def of(cls, req):
        if req is None:
            return None
        return cls(req.prefill_start_time, list(req.token_times),
                   list(req.generated), req.finish_reason)


def verdict(checks: Dict[str, Dict]) -> bool:
    """A run is correct when every number compared is within its limit."""
    return all(c["value"] <= c["limit"] for c in checks.values())


def check_served(reference, config: Dict, seed: int, sample: List[Record],
                 control: bool = False):
    """Hold the served tokens of ``sample`` to the float32 reference: the
    widest gap by which a served token's logit lies below the reference's
    best at its position, against the configuration's limit; and every
    sampled request served its whole budget (no stop token is set).

    With ``control``, the float8 control (the reference one precision
    step below the configuration's) is read at the same positions of the
    same prompts and served tokens, and the token it puts first at each
    position is held to the same check in the served token's place.
    Returns (checks, the control's checks or None)."""
    limit = config["limits"]["max_logit_gap"]
    if not sample:
        empty = {"max_logit_gap": {"value": math.inf, "limit": limit},
                 "short_answers": {"value": 0, "limit": 0}}
        return empty, (dict(empty) if control else None)
    seqs, rows, served = [], [], []
    for r in sample:
        seq, pos = reference.served_rows(r.prompt, r.generated)
        seqs.append(seq)
        rows.append(pos)
        served.append(np.asarray(r.generated, np.int64))
    t0 = time.perf_counter()
    ref_logits = reference.row_logits(config, seed, seqs, rows)
    log(f"reference: {len(sample)} requests, {sum(map(len, served))} "
        f"served tokens, longest {max(len(s) for s in seqs) + 1} tokens, "
        f"{time.perf_counter() - t0:.3f}s")

    def checks_of(tokens: List[np.ndarray]) -> Dict[str, Dict]:
        gaps = reference.logit_gaps(ref_logits, np.concatenate(tokens))
        short = sum(1 for r, t in zip(sample, tokens)
                    if len(t) != r.max_new_tokens)
        return {"max_logit_gap": {"value": float(gaps.max()),
                                  "limit": limit},
                "short_answers": {"value": short, "limit": 0}}

    checks = checks_of(served)
    if not control:
        return checks, None
    ctl = reference.row_logits(config, seed, seqs, rows, lowp=True)
    top = np.asarray(jax.numpy.argmax(ctl, axis=-1), np.int64)
    cuts = np.cumsum([len(t) for t in served])[:-1]
    return checks, checks_of(np.split(top, cuts))
