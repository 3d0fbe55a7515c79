"""One run of one benchmark cell on the chip.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Everything
particular to it is a file found by name: its configuration
(``configs[].file``), its traffic mix (``bench/traffic/<traffic>.json``,
read by ``bench/traffic/generator.py``) and one reader per metric
(``bench/metrics/<metric>.py``, ``read(run) -> value or None``).

The configuration file's keys:

* ``model``: the program's architecture (``repro.configs.get_arch``), and
  ``overrides``: fields of it replaced for this configuration;
* ``sizes``: the published sizes under their published names, each checked
  against the program's field that ``SIZE_FIELDS`` or the file's own
  ``size_fields`` (``{"<published name>": "<field>"}``) maps it to;
* ``semantics``: what the reference needs beyond the sizes;
* ``reference`` and ``work`` (optional): modules under ``bench/``, named by
  their path without ``.py``, that hold the model's plain forward (the
  contract is in ``bench/reference.py``) and its work counts (in
  ``bench/work.py``); those two files where the keys are absent;
* ``server``: the ``DiffusionServer``'s replicas, cache cap and session
  slots; ``check``: how many requests the check samples and its batch;
  ``limits``: the gap statistics that decide ``correct``, each with its
  limit;
* the rest (``source``, ``reduced``, ``assumed``, ``departures``, ...) is
  read by people, not by the harness.

A run makes the weights on the device from ``--seed``, builds the program's
``DiffusionServer`` on them, and serves every live session's first turn
(set-up, which also compiles or loads every program the window uses).  The
window then offers the mix's requests open loop: between ``step()`` calls
it submits every request that is due; after each ``step()`` it waits on each
served request's last logits in serving order and stamps its completion.
A request is timed from when it was due.  Where the mix says ``"drain":
true`` the window closes when every request due in its ``--seconds`` has
been served (never before ``--seconds``); with ``"drain": false`` (a mix
offered above capacity) it closes when the first ``step()`` that returns
after ``--seconds`` does, and the requests still queued are left unserved
and not counted as failed.  ``--trace 1`` records a profiler trace of a steady
part of the window and reports the per-layer metrics instead of the
end-to-end ones.

Once the window has closed, peak memory is read and the server freed, the
served tokens of a seeded sample of requests are held against the plain
reference (the configuration's reference module), over each one's whole
session history: at each served token, the gap between the reference's
best logit and its logit of that token.  ``correct`` is false if a
statistic of those gaps that the configuration's ``limits`` names exceeds
its limit, if the sessions' histories do not add up, or if a request was
lost (``verdict``).
The control (``bench/calibrate.py`` and the tests, never the benchmark's
runs) puts the token that the reference computed in fp8 ranks first in
each served token's place and goes through the same ``verdict``.  Each number
compared is printed beside its limit as the last lines of stderr and,
under ``checks``, last in the result: one JSON object, the last line of
stdout.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import gc
import glob
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "traffic"))

import reference  # noqa: E402
import trace_reduce  # noqa: E402
import work  # noqa: E402
from generator import schedule  # noqa: E402


def process_age_s() -> float:
    """Seconds since this process started (Linux ``/proc``)."""
    with open("/proc/self/stat") as f:
        fields = f.read().rsplit(")", 1)[1].split()
    started = int(fields[19]) / os.sysconf("SC_CLK_TCK")
    return time.clock_gettime(time.CLOCK_BOOTTIME) - started


def use_compile_cache() -> str:
    """JAX's persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` where
    set, else ``.jax_cache`` at the checkout's root (a fixed path, so the
    next run finds it).  Every program is cached, however quick to
    compile, so a warm run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileClock:
    """Backend compilations since entry (JAX's monitoring events)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        self.seconds = 0.0
        self.count = 0

    def _on(self, event: str, secs: float, **_: Any) -> None:
        if event == self.EVENT:
            self.seconds += secs
            self.count += 1

    def __enter__(self) -> "CompileClock":
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc: Any) -> None:
        jax.monitoring.unregister_event_duration_listener(self._on)


class Spans:
    """Harness spans: kept in memory and, while a trace is on, written to it
    (``TraceAnnotation``) so host work shares the device's clock."""

    def __init__(self) -> None:
        self.done: List[tuple] = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.done.append((name, t, time.perf_counter()))


# ------------------------------------------------------------------ cells
def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> Dict[str, Any]:
    """The cell's entry, configuration, mix and metric entries."""
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    return {
        "name": name,
        "chips": int(cell["chips"]),
        "config": load_json(os.path.join(ROOT, conf["file"])),
        "mix": load_json(os.path.join(BENCH, "traffic",
                                      f"{cell['traffic']}.json")),
        "end_to_end": [m for m in spec["end_to_end"]
                       if name in m.get("workloads", [name])],
        "per_layer": [m for m in spec["per_layer"]
                      if name in m.get("workloads", [name])],
    }


def _load(path: str, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str) -> Callable:
    return _load(os.path.join(BENCH, "metrics", f"{metric}.py"),
                 "bench_metric_" + metric.replace(".", "_")).read


@functools.lru_cache(maxsize=None)
def bench_module(name: str):
    """``bench/<name>.py``, loaded by path once a process, so that a
    function of it keeps its identity (and its compiled programs) across
    runs."""
    return _load(os.path.join(BENCH, f"{name}.py"),
                 "bench_" + name.replace("/", "_").replace(".", "_"))


# The modules a configuration file may name, by its key, and the module
# where it names none.
DEFAULT_MODULES = {"reference": reference, "work": work}


def config_module(config: Dict[str, Any], key: str):
    """The module the configuration file names under ``key``
    (``bench/<name>.py``), else ``DEFAULT_MODULES[key]``."""
    name = config.get(key)
    return DEFAULT_MODULES[key] if name is None else bench_module(name)


# The configuration file's stated sizes (the published names) and the
# program's fields they must equal; a file adds its own names under
# ``size_fields``.
SIZE_FIELDS = {
    "num_hidden_layers": "num_layers", "hidden_size": "d_model",
    "intermediate_size": "d_ff", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
    "vocab_size": "vocab_size", "num_experts": "num_experts",
    "num_experts_per_tok": "moe_top_k", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps"}


def model_config(config: Dict[str, Any]):
    """The program's configuration with the file's overrides, checked
    against the sizes the file states."""
    from repro.configs import get_arch
    cfg = dataclasses.replace(get_arch(config["model"]),
                              **config.get("overrides", {}))
    fields = {**SIZE_FIELDS, **config.get("size_fields", {})}
    for key, want in config["sizes"].items():
        if key not in fields:
            raise ValueError(f"{config['model']}: no field for the size "
                             f"{key!r}; map it under size_fields")
        if not hasattr(cfg, fields[key]):
            raise ValueError(f"{config['model']}: {key} maps to "
                             f"{fields[key]!r}, which the program's "
                             f"configuration lacks")
        got = getattr(cfg, fields[key])
        if got != want:
            raise ValueError(f"{config['model']}: {key} runs as {got}, the "
                             f"configuration file states {want}")
    return cfg


def reference_sizes(cfg, config: Dict[str, Any]) -> Dict[str, Any]:
    """What the reference needs: the fields its module names (``FIELDS``),
    and the semantics the configuration states."""
    fields = config_module(config, "reference").FIELDS
    m = {k: getattr(cfg, k) for k in fields}
    m.update(config["semantics"])
    return m


# ------------------------------------------------------------------ server
def build_server(cfg, weights, server: Dict[str, Any], devices):
    """The program's server, on the harness's weights.  The server draws its
    own parameters at construction; they are swapped for ``weights`` there,
    so both sides of the check run on weights the harness made."""
    from repro.runtime import serve_loop
    own = serve_loop.init_params
    serve_loop.init_params = lambda *_: weights
    try:
        return serve_loop.DiffusionServer(
            cfg, policy="good-cache-compute", payload="real",
            max_replicas=server["replicas"], min_replicas=server["replicas"],
            cache_cap=server["cache_cap"], max_sessions=server["hbm_sessions"],
            host_cache_sessions=server["dram_sessions"], devices=devices)
    finally:
        serve_loop.init_params = own


def make_model(config: Dict[str, Any], seed: int, devices):
    """The program's configuration and the seeded weights, on the device."""
    from repro.models import param_specs
    cfg = model_config(config)
    with jax.default_device(devices[0]):
        weights = reference.make_weights(param_specs(cfg), seed)
    jax.block_until_ready(weights)
    return cfg, weights


def warm_up(srv, sched, spans) -> "Window":
    """Serve the schedule's warm-up turns one at a time and wait for them."""
    warm = Window(srv, spans)
    for turn in sched.warmup:
        warm.submit(turn, time.perf_counter())
        warm.step()
    return warm


def counters(srv) -> Dict[str, float]:
    s = srv.stats
    out = {k: float(getattr(s, k)) for k in (
        "served", "prefix_hits", "swap_ins", "prefills", "decode_steps")}
    for row in srv.measured.rows():
        key = f"{row['src']}->{row['dst']}"
        out[key + ".bytes"] = row["bytes"]
        out[key + ".seconds"] = row["seconds"]
    return out


@dataclasses.dataclass
class Served:
    """One request as the harness saw it."""
    turn: Any
    req: Any
    submitted: float = 0.0          # perf_counter
    done: Optional[float] = None    # perf_counter after block_until_ready
    tokens: Optional[np.ndarray] = None
    rows: Optional[List[int]] = None   # positions whose logits gave tokens
    history: Optional[List[int]] = None  # session tokens after this request
    chain: Optional[List[int]] = None  # ids of the requests whose tokens
    #                                    the session holds, this one last


# ------------------------------------------------------------------ window
class Window:
    """Drives the server open loop and stamps completions."""

    def __init__(self, srv, spans: Spans):
        self.srv = srv
        self.spans = spans
        self.served: List[Served] = []
        self.outstanding: List[Served] = []
        self.step_began = float("-inf")    # perf_counter of the last step()

    def submit(self, turn, now: float) -> None:
        with self.spans("bench.submit"):
            req = self.srv.submit(turn.session, turn.prompt,
                                  max_new_tokens=turn.new_tokens)
        self.outstanding.append(Served(turn, req, submitted=now))

    def step(self) -> int:
        """One ``step()``; returns how many requests it completed."""
        self.step_began = time.perf_counter()
        with self.spans("bench.step"):
            self.srv.step()
        done = [s for s in self.outstanding if s.req.finish_time_s is not None]
        done.sort(key=lambda s: (s.req.finish_time_s, s.req.request_id))
        with self.spans("bench.sync"):
            for s in done:
                jax.block_until_ready(s.req.last_logits)
                s.done = time.perf_counter()
        self.outstanding = [s for s in self.outstanding if s.done is None]
        self.served.extend(done)
        return len(done)


# How long past the window's close a request may still complete; one that
# has not by then is lost.
GRACE_S = 60.0


def run_window(srv, sched, seconds: float, spans: Spans,
               trace_at: Optional[tuple] = None,
               drain: bool = True) -> Dict[str, Any]:
    """Offer ``sched.window`` open loop; returns the window's record.

    ``step()`` serves everything submitted before it, so a request still
    outstanding that was submitted before the last ``step()`` began was
    refused: it is lost.  Without ``drain`` the window closes at the first
    ``step()`` return after ``seconds``; the requests submitted since, and
    those due but not yet submitted, are ``queued``: left unserved, not
    lost."""
    w = Window(srv, spans)
    turns = sched.window
    i = 0
    stalled = False
    tracing, trace_dir = False, None
    t0 = time.perf_counter()
    with CompileClock() as clock:
        while True:
            now = time.perf_counter() - t0
            if trace_at is not None and not tracing and trace_dir is None \
                    and now >= trace_at[0]:
                trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # harness spans and JAX's own
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                tracing = True
            if tracing and now >= trace_at[1]:
                jax.profiler.stop_trace()
                tracing = False
            if not drain and now >= seconds:
                break
            due = i
            while i < len(turns) and turns[i].due_s <= now:
                w.submit(turns[i], time.perf_counter())
                i += 1
            if w.outstanding and (i > due or not stalled):
                stalled = w.step() == 0
                continue
            stalled = False
            if i == len(turns):
                if now >= seconds + (GRACE_S if w.outstanding else 0.0):
                    break
                nxt = seconds
            else:
                nxt = turns[i].due_s
            with spans("bench.wait"):
                time.sleep(max(0.0, min(nxt - now, 0.05)))
        if tracing:
            jax.profiler.stop_trace()
    t1 = time.perf_counter()
    lost: List[Served] = []
    queued = [t.due_s for t in turns[i:] if t.due_s < seconds]
    for s in w.outstanding:
        if drain or s.submitted < w.step_began:
            lost.append(s)
        else:
            queued.append(s.turn.due_s)
    return {"t0": t0, "t1": t1, "served": w.served, "lost": lost,
            "queued_due_s": queued, "compiles": clock.count,
            "compile_s": clock.seconds, "trace_dir": trace_dir}


# ------------------------------------------------------------------ chains
def build_chains(everything: List[Served], vocab: int) -> Optional[str]:
    """Reconstruct what each served request's session held: a prefix hit
    continues the state the same replica left for that session; a miss
    starts again from the prompt.  The decode loop feeds the prompt's last
    token and then its own tokens, so the logits at ``rows`` gave
    ``tokens``.  Returns a description of the first inconsistency."""
    held: Dict[tuple, tuple] = {}
    order = sorted(everything,
                   key=lambda s: (s.req.finish_time_s, s.req.request_id))
    for s in order:
        req = s.req
        key = (req.replica, req.session_id)
        prompt = [int(t) % vocab for t in s.turn.prompt]
        if req.prefix_hit:
            if key not in held:
                return (f"request {req.request_id}: a hit on {key} with no "
                        f"state served there")
            seq, chain = held[key]
        else:
            seq, chain = list(prompt), []
        gen = s.tokens
        if len(gen) != s.turn.new_tokens:
            return (f"request {req.request_id}: {len(gen)} of "
                    f"{s.turn.new_tokens} tokens served")
        s.rows = list(range(len(seq), len(seq) + len(gen)))
        seq = seq + [prompt[-1]] + [int(t) for t in gen[:-1]]
        chain = chain + [id(s)]
        s.history, s.chain = seq, chain
        held[key] = (seq, chain)
    return None


# ------------------------------------------------------------------ check
def check(weights, m: Dict[str, Any], everything: List[Served],
          sample: List[Served], cap: int, batch: int,
          control: bool, forward: Callable) -> tuple:
    """Statistics (``gap_stats``) of the gap, at every served token in the
    sampled requests' session histories, between the reference's best logit
    and its logit of the served token; and, with ``control``, the same with
    the token that the fp8 forward ranks first put in each served token's
    place (else None).  ``forward`` is the reference module's ``hidden``."""
    by_id = {id(s): s for s in everything}
    seqs = []
    for s in sample:
        rows, toks = [], []
        for link in s.chain:
            r = by_id[link]
            rows += r.rows
            toks += [int(t) for t in r.tokens]
        seqs.append((s.history, rows, toks))
    width = max(len(r) for _, r, _ in seqs)
    gaps, ctls = [], []
    for b in range(0, len(seqs), batch):
        blk = seqs[b:b + batch]
        blk += [blk[-1]] * (batch - len(blk))
        tokens = np.zeros((batch, cap), np.int32)
        rows = np.zeros((batch, width), np.int32)
        served = np.zeros((batch, width), np.int32)
        valid = np.zeros((batch, width), bool)
        for j, (hist, r, t) in enumerate(blk):
            tokens[j, :len(hist)] = hist
            rows[j, :len(r)] = r
            served[j, :len(t)] = t
            valid[j, :len(r)] = True
        gap, ctl = reference.served_gaps(weights, tokens, rows, served, m,
                                         control=control, forward=forward)
        n = len(seqs) - b
        gaps += [gap[j][valid[j]] for j in range(min(n, batch))]
        if control:
            ctls += [ctl[j][valid[j]] for j in range(min(n, batch))]
    out = gap_stats(np.concatenate(gaps))
    if not control:
        return out, None
    # per sampled session: served tokens, mean and widest gap
    out["per_session"] = [(len(g), round(float(g.mean()), 5),
                           round(float(g.max()), 4)) for g in gaps]
    return out, gap_stats(np.concatenate(ctls))


def gap_stats(g: np.ndarray) -> Dict[str, float]:
    """What a configuration's limits may hold the served tokens' gaps to."""
    out = {"tokens_checked": int(g.size),
           "max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
           "p99_logit_gap": float(np.percentile(g, 99)),
           "share_off_best": float((g > 0).mean())}
    for t in (0.05, 0.1, 0.2):
        out[f"share_gap_over_{t}"] = float((g > t).mean())
    return out


def verdict(problem: Optional[str], lost: int,
            got: Optional[Dict[str, float]],
            limits: Dict[str, float]) -> tuple:
    """``correct`` and the numbers compared, each beside its limit: no
    request lost, the sessions' histories consistent, and every statistic
    the configuration's ``limits`` names within its limit."""
    checks = {"lost_requests": {"value": lost, "limit": 0}}
    if problem is not None or got is None:
        checks["consistent_sessions"] = {"value": 0, "limit": 1}
        return False, checks
    for name, limit in limits.items():
        checks[name] = {"value": got[name], "limit": limit}
    correct = lost == 0 and all(checks[name]["value"] <= limit
                                for name, limit in limits.items())
    return correct, checks


def _fmt(got: Dict[str, Any]) -> str:
    return " ".join(f"{k}={v:.6g}" if isinstance(v, float) else f"{k}={v}"
                    for k, v in got.items())


def pick_sample(window: List[Served], n: int, seed: int,
                prompt_lens: List[int]) -> List[Served]:
    """``n`` requests drawn from the seed, plus the one with the longest
    history and, for each prompt length, the longest history built on it."""
    rng = np.random.default_rng(int(seed) + 1)
    chosen = {id(s): s for s in (window[i] for i in rng.choice(
        len(window), size=min(n, len(window)), replace=False))}
    longest = max(window, key=lambda s: len(s.history))
    chosen[id(longest)] = longest
    for plen in prompt_lens:
        cands = [s for s in window if len(s.turn.prompt) == plen]
        if cands:
            s = max(cands, key=lambda s: len(s.history))
            chosen[id(s)] = s
    return list(chosen.values())


# ------------------------------------------------------------------ run
class Run:
    """What a metric reader sees; ``work`` is the configuration's work
    counts module (``decode_call``, ``prefill_call``)."""

    def __init__(self, **kw: Any):
        self.__dict__.update(kw)
        self._cache: Dict[str, Any] = {}

    def metric(self, name: str) -> Optional[float]:
        if name not in self._cache:
            self._cache[name] = load_reader(name)(self)
        return self._cache[name]

    @property
    def peak(self) -> Dict[str, float]:
        """The chip's peaks (``bench/peaks.json``); an unknown kind raises."""
        return work.peaks(self.device_kind)

    def latencies_s(self) -> List[float]:
        """Due time to completion of every request due in the window; a
        request never served counts as infinitely late, one queued at the
        close as done at the close."""
        t0, t1 = self.window["t0"], self.window["t1"]
        return ([s.done - t0 - s.turn.due_s for s in self.served]
                + [t1 - t0 - due for due in self.window["queued_due_s"]]
                + [float("inf")] * len(self.window["lost"]))

    def outstanding(self) -> List[tuple]:
        """Intervals (``perf_counter``) with at least one request due and
        not yet complete (a request queued at the close, until the close)."""
        t0, t1 = self.window["t0"], self.window["t1"]
        return trace_reduce.merge(
            [(t0 + s.turn.due_s, s.done) for s in self.served]
            + [(t0 + due, t1) for due in self.window["queued_due_s"]])

    def decode_contexts(self) -> List[int]:
        """Positions attended by each decode step of the window."""
        return [r + 1 for s in self.served for r in s.rows]

    def prefill_lengths(self) -> List[int]:
        """Prompt lengths of the window's requests that ran a prefill."""
        return [len(s.turn.prompt) for s in self.served
                if not s.req.prefix_hit]

    def trace_outstanding(self) -> List[tuple]:
        """``outstanding()`` on the trace's clock, inside the traced
        window."""
        off = trace_reduce.offset(self.trace, self.spans)
        lo, hi = trace_reduce.window(self.trace)
        return trace_reduce.intersect(
            [(a + off, b + off) for a, b in self.outstanding()], [(lo, hi)])


def run_cell(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
             devices, log: Callable[[str], None],
             control: bool = False) -> Dict[str, Any]:
    """One run of ``cell`` (as ``load_cell`` gives it) on ``devices``."""
    config, mix = cell["config"], cell["mix"]
    cfg, weights = make_model(config, seed, devices)
    m = reference_sizes(cfg, config)
    cap = int(config["server"]["cache_cap"])
    srv = build_server(cfg, weights, config["server"], devices)
    sched = schedule(mix, seed, seconds, cfg.vocab_size, cap)
    spans = Spans()
    warm = warm_up(srv, sched, spans)
    before = counters(srv)
    setup_s = process_age_s()
    trace_at = None
    if trace:
        span = float(mix["trace_s"])
        start = min(0.4 * seconds, max(0.0, seconds - span))
        trace_at = (start, start + span)
    rec = run_window(srv, sched, seconds, spans, trace_at,
                     drain=bool(mix["drain"]))
    after = counters(srv)
    stats = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)
    log(f"window: {len(sched.window)} due, {len(rec['served'])} served, "
        f"{len(rec['lost'])} lost, {len(rec['queued_due_s'])} queued at the "
        f"close, {rec['t1'] - rec['t0']:.3f} s; "
        f"generator late by max "
        f"{max([s.submitted - rec['t0'] - s.turn.due_s for s in rec['served']] or [0]) * 1e3:.1f} ms; "
        f"compilations in window {rec['compiles']} ({rec['compile_s']:.3f} s)")
    log("counters: " + " ".join(f"{k}={v:g}" for k, v in stats.items()))
    log(f"setup_s={setup_s:.3f} memory_peak_bytes={peak}")

    everything = warm.served + rec["served"]
    for s in everything:
        s.tokens = np.concatenate([np.asarray(t) for t in s.req.generated])
    problem = build_chains(everything, cfg.vocab_size)
    traced = None
    if rec["trace_dir"]:
        files = glob.glob(os.path.join(rec["trace_dir"], "**", "*.xplane.pb"),
                          recursive=True)
        traced = trace_reduce.load(files[0])
        shutil.rmtree(rec["trace_dir"], ignore_errors=True)
    run = Run(cfg=cfg, config=config, mix=mix, seconds=seconds,
              work=config_module(config, "work"),
              device_kind=devices[0].device_kind,
              setup_s=setup_s, stats=stats, window=rec,
              served=rec["served"], trace=traced, spans=spans.done)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for spec in cell[kind]:
        value = run.metric(spec["name"])
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}

    # free the program's state before the reference runs
    for s in everything:
        s.req = dataclasses.replace(s.req, generated=[], last_logits=None)
    del srv, warm, rec
    gc.collect()
    attempted = len(sched.window)
    lost = len(run.window["lost"])
    limits = config["limits"]
    got = ctl = None
    if problem is None and run.served:
        sample = pick_sample(run.served, int(config["check"]["requests"]),
                             seed, mix["prompt_lens"])
        got, ctl = check(weights, m, everything, sample, cap,
                         int(config["check"]["batch"]), control,
                         config_module(config, "reference").hidden)
        log(f"check: {len(sample)} requests, {got['tokens_checked']} served "
            f"tokens held to the reference: " + _fmt(got))
        if ctl is not None:
            log("control: the fp8 reference's first choices in their "
                "place: " + _fmt(ctl))
    else:
        log(f"check: {problem or 'nothing served'}")
    correct, checks = verdict(problem, lost, got, limits)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": attempted, "failed": lost,
           "metrics": metrics, "device": device}
    if traced is not None:
        lo, hi = trace_reduce.window(traced)
        device.update(busy_s=trace_reduce.busy_s(traced, (lo, hi)),
                      window_s=hi - lo)
        out["breakdown"] = {"device_ops": trace_reduce.top_ops(traced),
                            "idle_gaps": trace_reduce.idle_gaps(traced,
                                                                (lo, hi))}
    if control:
        ok, ctl_checks = verdict(problem, lost, ctl, limits)
        out["control"] = {"correct": ok, "checks": ctl_checks}
    out["checks"] = checks
    return out


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import repro  # noqa: F401  (the system under test must be present)
    cache = use_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX platform is {devices[0].platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2

    def log(line: str) -> None:
        print(line, file=sys.stderr, flush=True)

    log(f"compile cache: {cache}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   devices[:cell["chips"]], log)
    for name, c in out["checks"].items():
        log(f"{name} {c['value']} limit {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
