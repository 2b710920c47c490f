"""Driver for traffic of ``kind: build``: one ``gordo fleet-build`` job.

The job runs on a worker thread through the program's own entry,
``cli.gordo.main(["fleet-build", ...])``: provider fetch and assembly,
ingest, the fleet train program, artifact commit. This thread watches the
program's counter ``gordo_fleet_machines_total``: a slice has committed when
the count of resolved machines reaches a multiple of the slice size.

* set-up ends, and the window opens, at the commit of the job's first slice
  (compile or cache load are behind it, the prefetcher overlaps the next);
* the window closes at the first slice commit at or after ``--seconds``;
* ``machines_per_hour`` is the machines committed between those two events
  over the wall time between them;
* the job is told to end (``StopBuild`` from the benchmark's dataset class
  ends ``build_fleet`` at the next slice boundary): at the window's close,
  or already at its open where one slice is sure to fill it; then the peak
  memory is read, the trace is reduced, and a sample of the machines the job
  committed inside the window is compared with the plain reference.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pandas as pd

from benchmarks import harness
from benchmarks.harness import log

POLL_S = 0.01
# a trace stops this long after its stretch's end, so that the end is in it
TRACE_EDGE_S = 0.02
COUNTER = "gordo_fleet_machines_total"


# ------------------------------------------------------------- the job ----
def fleet_config(run: Dict[str, Any]) -> Dict[str, Any]:
    config, traffic, seed = run["config"], run["traffic"], run["seed"]
    start = np.datetime64(traffic["train_start_date"][:19])
    end = start + np.timedelta64(int(traffic["history_days"]), "D")
    names = machine_names(run)
    return {
        "project-name": f"bench-{config['name']}",
        "machines": [
            {
                "name": name,
                "dataset": {
                    "machine": name,
                    "tag_list": tag_names(name, config["tags"]),
                },
            }
            for name in names
        ],
        "globals": {
            "model": config["model"],
            "dataset": {
                "type": "benchmarks.data.dataset.TimedDataset",
                "train_start_date": traffic["train_start_date"],
                "train_end_date": str(end) + "+00:00",
                "resolution": traffic["resolution"],
                "interpolation_limit": traffic["interpolation_limit"],
                "data_provider": {
                    "type": "benchmarks.data.provider.SeededProvider",
                    "seed": seed,
                    **traffic["provider"],
                },
            },
        },
    }


def sizes(run: Dict[str, Any]) -> Tuple[int, int]:
    """(slice size, fleet height): the traffic's own where it states them (a
    rehearsal), else the configuration's, which sized them from its memory
    figures."""
    config, traffic = run["config"], run["traffic"]
    return (
        int(traffic.get("slice_size", config["slice_size"])),
        int(traffic.get("fleet_machines", config["fleet_machines"])),
    )


def machine_names(run: Dict[str, Any]) -> List[str]:
    _, height = sizes(run)
    return [f"s{run['seed']}-m{i:04d}" for i in range(height)]


def tag_names(machine: str, tags: int) -> List[str]:
    return [f"{machine}-t{j:02d}" for j in range(tags)]


class Job:
    """``gordo fleet-build`` on a worker thread."""

    def __init__(self, argv: List[str]):
        self.argv = argv
        self.outcome: Optional[str] = None  # "stopped" | "finished" | error text
        self.thread = threading.Thread(target=self._main, daemon=True, name="bench-job")

    def _main(self) -> None:
        from benchmarks.data.dataset import StopBuild
        from gordo_components_tpu.cli import gordo

        try:
            gordo.main(self.argv, standalone_mode=False)
            self.outcome = "finished"
        except StopBuild:
            self.outcome = "stopped"
        except SystemExit as exc:
            self.outcome = f"fleet-build exited {exc.code}"
        except BaseException as exc:  # reported by the run, which then fails
            self.outcome = f"fleet-build raised {type(exc).__name__}: {exc}"


def resolved_counts() -> Dict[str, int]:
    from gordo_components_tpu.observability.registry import REGISTRY

    values = REGISTRY.counter(COUNTER, labels=("outcome",)).collect()
    return {key[0]: int(value) for key, value in values.items()}


class Watch:
    """Commit events of the job. The program's counter says THAT a slice has
    committed (polled, so late by up to a poll gap, which the job's threads
    stretch through the interpreter lock); WHEN it did is read off the store:
    the modification time of the ``CURRENT`` pointer of the slice's last
    machine, which the program writes as that machine's generation commits."""

    def __init__(self, job: Job, slice_size: int, out_dir: str, names: List[str]):
        self.job, self.slice_size = job, slice_size
        self.out_dir, self.names = out_dir, names
        self.base = resolved_counts()
        self.commits: List[float] = []  # commits[k]: when slice k had committed
        self.seen: List[float] = []  # when the poll saw it
        self.worst_poll = 0.0
        self._last_poll = time.perf_counter()
        # file times are on the wall clock; everything else on perf_counter
        self._wall_to_perf = time.perf_counter() - time.time()

    def _stamp(self, machine: int) -> Optional[float]:
        path = os.path.join(self.out_dir, self.names[machine], "CURRENT")
        try:
            return os.stat(path).st_mtime_ns * 1e-9 + self._wall_to_perf
        except OSError:  # the machine failed: no artifact
            return None

    def _committed_at(self, index: int, seen: float) -> float:
        stamp = self._stamp((index + 1) * self.slice_size - 1)
        return seen if stamp is None else stamp

    def slice_phases(self) -> List[Tuple[float, float]]:
        """For each committed slice after the first, off the store's file
        times: seconds from the slice before's last commit to this one's
        first (the job's ingest, train program and result fetch), and from
        its first commit to its last (the commit loop)."""
        out = []
        for k in range(1, len(self.commits)):
            first = self._stamp(k * self.slice_size)
            if first is not None:
                out.append((first - self.commits[k - 1], self.commits[k] - first))
        return out

    def poll(self) -> None:
        counts = resolved_counts()
        now = time.perf_counter()
        self.worst_poll = max(self.worst_poll, now - self._last_poll)
        self._last_poll = now
        done = sum(
            counts.get(k, 0) - self.base.get(k, 0) for k in ("completed", "failed")
        )
        while done >= (len(self.commits) + 1) * self.slice_size:
            self.seen.append(now)
            self.commits.append(self._committed_at(len(self.commits), now))

    def wait_commit(self, index: int, also_after: float = 0.0) -> Optional[int]:
        """Block until slice ``index`` has committed and a slice has committed
        at or after ``also_after``; returns the index of the first such
        commit, or ``None`` if the job ended first."""
        while True:
            self.poll()
            hit = next(
                (k for k in range(index, len(self.commits))
                 if self.commits[k] >= also_after), None,
            )
            if hit is not None:
                return hit
            if not self.job.thread.is_alive():
                self.poll()
                return next(
                    (k for k in range(index, len(self.commits))
                     if self.commits[k] >= also_after), None,
                )
            time.sleep(POLL_S)


# ----------------------------------------------------------- the check ----
def read_artifact(model_dir: str, probe: np.ndarray) -> Dict[str, Any]:
    """What the job committed for one machine, loaded back through the
    program's store and serializer, and its anomaly output on ``probe``."""
    from gordo_components_tpu import serializer
    from gordo_components_tpu.models.analysis import analyze_model

    model = serializer.load(model_dir)
    metadata = serializer.load_metadata(model_dir)
    parts = analyze_model(model)
    frame = model.anomaly(probe)
    cv = parts.detector.cross_validation_
    return {
        "params": _to_numpy(parts.estimator.params_),
        "loss_history": np.asarray(parts.estimator.history_, np.float64),
        "input_scale": np.asarray(parts.input_scaler.params_.scale),
        "input_offset": np.asarray(parts.input_scaler.params_.offset),
        "target_scale": np.asarray(parts.target_scaler.params_.scale),
        "error_scale": np.asarray(parts.detector.scaler.params_.scale),
        "error_offset": np.asarray(parts.detector.scaler.params_.offset),
        "cv_mse": np.asarray(
            [s["scores"]["mean_squared_error"] for s in cv["splits"]], np.float64
        ),
        "total_threshold": float(parts.detector.total_threshold_),
        "anomaly_mean": float(np.mean(frame["total-anomaly-score"].values)),
        "x_shape": metadata["dataset"]["x_shape"],
    }


def padded_rows(fetches: Dict[str, Dict]) -> int:
    """The row count the job trained at: the program rounds a slice's longest
    machine up to its row quantum (256); read off the fetches it made."""
    longest = max(f["rows"] for f in fetches.values())
    return -(-longest // 256) * 256


def _to_numpy(tree):
    if isinstance(tree, dict):
        return {k: _to_numpy(v) for k, v in tree.items()}
    return np.asarray(tree)


def program_keys(seed: int, slice_index: int, n_padded: int):
    """The keys ``build_fleet --seed`` hands the machines of one slice of the
    first bucket: the program's documented derivation, followed here so that
    the reference starts from the same seed."""
    import jax

    master = jax.random.PRNGKey(seed)
    return jax.random.split(
        jax.random.fold_in(jax.random.fold_in(master, 0), slice_index), n_padded
    )


def reference_results(
    run: Dict[str, Any], sample: List[int], n_rows: int, dtype=None,
    precision: Optional[str] = "highest", fault: Optional[str] = None,
) -> List[Dict[str, Any]]:
    """The plain reference's build of the sampled machines (indices into the
    fleet), at the padded row count the job trained at."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import build as ref_build
    from benchmarks.reference import data as ref_data

    config, traffic = run["config"], run["traffic"]
    slice_size, _ = sizes(run)
    model = config["reference_model"]
    names = machine_names(run)
    start_ns = int(np.datetime64(traffic["train_start_date"][:19], "ns").astype(np.int64))
    end_ns = start_ns + int(traffic["history_days"]) * 86_400 * 10**9
    resolution_ns = int(pd.Timedelta(traffic["resolution"]).value)
    limit_bins = int(pd.Timedelta(traffic["interpolation_limit"]).value) // resolution_ns
    stacked_X, stacked_w, keys, raws = [], [], [], []
    for index in sample:
        raw = ref_data.assemble(
            tag_names(names[index], config["tags"]), run["seed"], start_ns,
            end_ns, resolution_ns, traffic["provider"]["min_size"],
            traffic["provider"]["max_size"], limit_bins,
        )
        if dtype is not None:  # the control lowers the data's precision too
            import ml_dtypes

            raw = raw.astype(ml_dtypes.bfloat16).astype(np.float32)
        X = np.zeros((n_rows, raw.shape[1]), np.float32)
        w = np.zeros((n_rows,), np.float32)
        X[n_rows - len(raw):] = raw
        w[n_rows - len(raw):] = 1.0
        stacked_X.append(X)
        stacked_w.append(w)
        raws.append(raw)
        slice_index, position = divmod(index, slice_size)
        keys.append(np.asarray(program_keys(run["seed"], slice_index, slice_size)[position]))
    dtype = jnp.dtype(jnp.float32 if dtype is None else dtype)
    build, anomaly, initial = ref_build.make_build(
        model, n_rows, config["tags"], dtype=dtype, fault=fault
    )
    probe_rows = int(traffic["probe_rows"])

    def one(X, w, key, probe):
        result = build(X, w, key)
        result["anomaly_mean"] = anomaly(result, probe)
        return result

    probes = np.stack([raw[-probe_rows:] for raw in raws])
    args = (np.stack(stacked_X), np.stack(stacked_w), np.stack(keys), probes)
    with jax.default_matmul_precision(precision) if precision else contextlib.nullcontext():
        started = time.perf_counter()
        lowered = jax.jit(jax.vmap(one)).lower(*args)
        lowered_at = time.perf_counter()
        compiled = lowered.compile()
        compiled_at = time.perf_counter()
        room_on_device(compiled, "the plain reference's build")
        out = jax.device_get(compiled(*args))
        # what every fit started from: a program of its own, so that the
        # build holds one copy of a model less
        out["params0"] = jax.device_get(jax.jit(jax.vmap(initial))(args[2]))
    log(f"the plain reference's build: traced and lowered in {lowered_at - started:.1f}s, "
        f"compiled or loaded in {compiled_at - lowered_at:.1f}s, ran and fetched in "
        f"{time.perf_counter() - compiled_at:.1f}s")
    results = []
    for i, raw in enumerate(raws):
        # the machine's scalars and vectors are widened; its parameters stay
        # float32 leaves (a whole tree is never widened on the host)
        result = {
            key: np.asarray(value[i], np.float64)
            for key, value in out.items() if key not in ("params", "params0")
        }
        for key in ("params", "params0"):
            result[key] = jax.tree_util.tree_map(lambda a: a[i], out[key])
        # an autoencoder's targets are its inputs: one scaler stands for both
        result["target_scale"] = result["input_scale"]
        result["rows"] = len(raw)
        result["x_sum"] = float(np.asarray(raw, np.float64).sum())
        result["probe"] = raw[-probe_rows:]
        results.append(result)
    return results


def room_on_device(compiled, what: str) -> None:
    """Say on stderr what the device holds and what ``compiled`` will ask of
    it; where the two do not fit the device's limit, end the run here with
    both numbers named, not later with the allocator's text. A backend that
    keeps no such figures (the CPU) is not asked."""
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    analysis = compiled.memory_analysis()
    if analysis is None or "bytes_limit" not in stats:
        return
    needs = (
        analysis.argument_size_in_bytes + analysis.output_size_in_bytes
        + analysis.temp_size_in_bytes - analysis.alias_size_in_bytes
    )
    in_use, limit = int(stats.get("bytes_in_use", 0)), int(stats["bytes_limit"])
    log(
        f"{what}: the device holds {in_use} bytes in use of {limit}; the compiled "
        f"program needs {needs} (arguments {analysis.argument_size_in_bytes}, outputs "
        f"{analysis.output_size_in_bytes}, temporaries {analysis.temp_size_in_bytes})"
    )
    if in_use + needs > limit:
        log(
            f"NO ROOM for {what}: {needs} bytes needed beside {in_use} in use "
            f"are {in_use + needs - limit} over the device's {limit}; no result"
        )
        raise SystemExit(8)


def replay_anomaly(run: Dict[str, Any], built: List[Dict[str, Any]], probes) -> None:
    """``anomaly_replayed`` of each built machine: the mean anomaly score of
    its probe rows as the plain reference's arithmetic (float32 at
    ``highest``) gives it from that machine's OWN committed parameters and
    scalers. Beside the machine's ``anomaly_mean`` (the loaded model's
    ``anomaly()``) it holds the serving side of an artifact to plain
    arithmetic on the same weights: one forward pass apart, where two
    trainings would be a whole fit apart. Whether the weights are the right
    ones is the other numbers' to say, from the reference's own training."""
    import jax
    import jax.numpy as jnp

    from benchmarks.reference import build as ref_build

    config = run["config"]
    probes = np.asarray(probes, np.float32)
    _, anomaly, _ = ref_build.make_build(
        config["reference_model"], probes.shape[1], config["tags"], dtype=jnp.float32
    )
    keys = ("params", "input_scale", "input_offset", "error_scale", "error_offset")
    stacked = jax.tree_util.tree_map(
        lambda *leaves: np.stack([np.asarray(leaf, np.float32) for leaf in leaves]),
        *[{k: machine[k] for k in keys} for machine in built],
    )
    with jax.default_matmul_precision("highest"):
        out = jax.device_get(jax.jit(jax.vmap(anomaly))(stacked, probes))
    for machine, value in zip(built, out):
        machine["anomaly_replayed"] = float(value)


# What readings and tests put in the program's place: the plain reference
# itself, built another way. Each name maps to ``reference_results``' own
# arguments.
STAND_INS = {
    # the same mathematics at the precision the configuration states
    # (float32, matmuls at JAX's default: one bf16 pass on the chip), in
    # code, batching and reduction order that share nothing with the
    # program's: what a sound rewrite of the program may read. No fault.
    "sound_default_precision": {"precision": None},
    # the control: the nearest precision below the configuration's
    "control_bf16": {"dtype": "bfloat16", "precision": None},
    # the faults ``reference/build.py`` can plant
    "half_batch": {"fault": "half_batch"},
    "state_unchanged": {"fault": "state_unchanged"},
}


def stand_in_numbers(
    run: Dict[str, Any], sample: List[int], n_rows: int,
    references: List[Dict[str, Any]], variant: str,
) -> List[Dict[str, float]]:
    """Per-machine numbers of one stand-in against the reference."""
    from benchmarks.reference import compare

    if variant not in STAND_INS:
        raise SystemExit(f"bench: no stand-in or fault named {variant!r}")
    stood_in = reference_results(run, sample, n_rows, **STAND_INS[variant])
    replay_anomaly(run, stood_in, [s["probe"] for s in stood_in])
    return [compare.machine_numbers(a, b) for a, b in zip(stood_in, references)]


def correct_rules(run: Dict[str, Any]) -> Dict[str, Any]:
    """The configuration's ``correct`` block. A rehearsal mix states what
    belongs to its size and not the cell's: how many machines it compares,
    and a limit where a planted fault shows less in its short history than
    in the cell's (on the CPU sound runs read 1e-7, so there is room)."""
    rules, traffic = dict(run["config"]["correct"]), run["traffic"]
    rules["check_machines"] = traffic.get("check_machines", rules["check_machines"])
    rules["limits"] = {**rules["limits"], **traffic.get("limits", {})}
    return rules


def sample_of(run: Dict[str, Any], committed: List[int]) -> List[int]:
    """The machines a run compares: drawn from the seed among those the job
    committed inside the window."""
    count = int(correct_rules(run)["check_machines"])
    rng = np.random.default_rng(run["seed"])
    return sorted(rng.choice(committed, size=min(count, len(committed)), replace=False).tolist())


def check(run: Dict[str, Any], committed: List[int], out_dir: str, fetches: Dict[str, Dict]) -> Dict[str, Any]:
    """Compare a sample of the machines the job committed inside the window,
    drawn from the seed, with the plain reference."""
    from benchmarks.reference import compare

    rules = correct_rules(run)
    sample = sample_of(run, committed)
    names = machine_names(run)
    started = time.perf_counter()
    n_rows = padded_rows(fetches)
    references = reference_results(run, sample, n_rows)
    reference_s = time.perf_counter() - started
    programs = []
    for index, reference in zip(sample, references):
        name = names[index]
        program = read_artifact(os.path.join(out_dir, name), reference["probe"])
        program["rows"] = fetches[name]["rows"]
        program["x_sum"] = fetches[name]["sum"]
        if program["x_shape"][0] != program["rows"]:
            program["rows"] = -1  # the artifact and the fetch disagree
        programs.append(program)
    replay_anomaly(run, programs, [r["probe"] for r in references])
    per_machine = [compare.machine_numbers(a, b) for a, b in zip(programs, references)]
    for index, numbers in zip(sample, per_machine):
        log(f"machine {names[index]}: " + " ".join(f"{k}={v:.6g}" for k, v in numbers.items()))
    judged = compare.judge_sample(per_machine, rules)
    variants, variants_per_machine = {}, {}
    for variant in run.get("variants", ()):
        variants_per_machine[variant] = stand_in_numbers(run, sample, n_rows, references, variant)
        variants[variant] = compare.judge_sample(variants_per_machine[variant], rules)
    return {
        "variants": variants,
        "variants_per_machine": variants_per_machine,
        "sample": [names[i] for i in sample],
        "judged": judged,
        "per_machine": per_machine,
        "reference_s": reference_s,
        "check_s": time.perf_counter() - started,
    }


# ------------------------------------------------------------- the run ----
def run_cell(run: Dict[str, Any]) -> Dict[str, Any]:
    """Drive one run; returns what ``run.py`` prints."""
    import jax

    from benchmarks.data.dataset import RECORDER
    from gordo_components_tpu.utils.backend import enable_persistent_compile_cache

    deadline, meter = run["deadline"], run["meter"]
    traffic, config = run["traffic"], run["config"]
    slice_size, _ = sizes(run)
    deadline.limit("setup_budget_s", float(traffic["setup_budget_s"]))
    deadline.limit("run_budget_s", float(traffic["run_budget_s"]))

    deadline.enter("config")
    cache_dir = enable_persistent_compile_cache()
    work = os.path.join(harness.WORK_ROOT, run["cell"]["name"])
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    config_path = os.path.join(work, "fleet.json")
    with open(config_path, "w") as fh:
        json.dump(fleet_config(run), fh)
    out_dir = os.path.join(work, "models")
    RECORDER.reset()
    job = Job([
        "--log-level", "WARNING", "fleet-build",
        "--machine-config", config_path, "--output-dir", out_dir,
        "--n-splits", str(config["n_splits"]), "--seed", str(run["seed"]),
        "--slice-size", str(slice_size),
    ])
    watch = Watch(job, slice_size, out_dir, machine_names(run))

    # -- set-up: until the job's first slice has committed -----------------
    deadline.enter("job: preamble, compile or cache load, first slice")
    job_started = time.perf_counter()
    job.thread.start()
    with jax.profiler.TraceAnnotation("bench:warm"):
        opened = watch.wait_commit(0)
    if opened is None:
        return early_end(run, job, watch, "before the window opened")
    t_open = watch.commits[opened]
    deadline.limit("setup_budget_s", None)
    deadline.enter("window")
    setup_s = t_open - run["started"]
    compile_before = meter.between(0.0, t_open)
    log(
        f"set-up {setup_s:.2f}s (job started at {job_started - run['started']:.2f}s, "
        f"first fetch at {(RECORDER.first_fetch_at or t_open) - run['started']:.2f}s); "
        f"compiles before the window: {compile_before}; cache dir {cache_dir}"
    )
    # A slice in flight cannot be stopped, so a job told to end at the
    # window's close trains one slice more. Where the first slice took
    # twice --seconds or more, the slice now starting closes the window
    # whatever happens: the job is told now, makes the next slice's fetches
    # beside this one as ever (StopBuild comes at their end), and ends at
    # the window's closing commit.
    warm_slice_s = first_slice_seconds(watch, meter)
    if warm_slice_s >= 2.0 * run["seconds"]:
        RECORDER.stop.set()
    log(
        f"the first slice took {warm_slice_s:.1f}s from its program's load to "
        f"its commit: the job is told to end "
        f"{'now, at the open' if RECORDER.stop.is_set() else 'at the close'}"
    )

    # -- the window ---------------------------------------------------------
    trace_dir = os.path.join(work, "trace")
    trace_times: Optional[Dict[str, float]] = None
    if run["trace"]:
        # the device's own events, the harness's marks and the program's
        # spans; no Python call tracing, which slows the job's host side and
        # swells the trace
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        with jax.profiler.TraceAnnotation("bench:sync"):
            sync_at = time.perf_counter()
        # The stretch is whole commit periods by the store's file times,
        # trace_min_s or more of them, laid as far behind its two commits
        # as the trace's start lay behind the first: the poll's lag and the
        # profiler's start. The trace is held open that long past the
        # closing commit, wherever the device's runs fall against either.
        first = len(watch.commits) - 1  # the newest commit the poll has seen
        lag = sync_at - watch.commits[first]
        traced = watch.wait_commit(
            first + 1, also_after=watch.commits[first] + float(traffic["trace_min_s"])
        )
        if traced is not None:
            time.sleep(max(0.0, watch.commits[traced] + lag + TRACE_EDGE_S - time.perf_counter()))
        trace_to = time.perf_counter()
        if traced is not None and watch.commits[traced] >= t_open + run["seconds"]:
            # the window closes at this commit: let the job reach its next
            # slice boundary while the profiler hands the trace over
            RECORDER.stop.set()
        trace_data = stop_trace_in_memory(trace_dir)
        log(f"trace stopped and read in {time.perf_counter() - trace_to:.1f}s")
        if traced is None:
            return early_end(run, job, watch, "inside the traced stretch")
        trace_times = {
            "periods": traced - first, "ran_s": trace_to - sync_at,
            "stretch_s": watch.commits[traced] - watch.commits[first],
        }
        log(
            f"traced stretch: {traced - first} commit period(s), slice {first} to "
            f"{traced}, {trace_times['stretch_s']:.3f}s by the store's file times, laid "
            f"{lag:.3f}s behind its commits; the trace ran {trace_times['ran_s']:.3f}s"
        )
    with jax.profiler.TraceAnnotation("bench:window"):
        closed = watch.wait_commit(opened + 1, also_after=t_open + run["seconds"])
    if closed is None:
        return early_end(run, job, watch, "before the window could close")
    t_close = watch.commits[closed]
    window_s = t_close - t_open
    machines = (closed - opened) * slice_size

    # -- end the job between slices, then look ------------------------------
    deadline.enter("stopping the job")
    RECORDER.stop.set()
    job.thread.join(timeout=300.0)
    stopped_s = time.perf_counter() - t_close
    if job.thread.is_alive() or job.outcome not in ("stopped", "finished"):
        log(f"the job did not end between slices: outcome {job.outcome!r}")
        raise SystemExit(5)
    compile_inside = meter.between(t_open, t_close)
    device = dict(run["device"])
    stats = jax.local_devices()[0].memory_stats() or {}
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.local_devices()
    ]
    if all(p is not None for p in peaks):
        device["memory_peak_bytes"] = int(max(peaks))
    log(
        f"window {window_s:.3f}s for --seconds {run['seconds']}: slices "
        f"{opened + 1}..{closed} ({machines} machines); job ended {stopped_s:.2f}s "
        f"after the close; worst poll gap {watch.worst_poll * 1e3:.1f} ms, commits seen "
        f"{max(a - b for a, b in zip(watch.seen, watch.commits)) * 1e3:.1f} ms late at "
        f"worst (the window's ends are read off the store's own file times); compiles "
        f"inside the window: {compile_inside}; bytes_in_use now {stats.get('bytes_in_use')}"
    )

    watch.poll()
    log("slices after the first, seconds before their first commit + in the commit loop: "
        + ", ".join(f"{a:.2f}+{b:.2f}" for a, b in watch.slice_phases()))

    with open(os.path.join(out_dir, "fleet_manifest.json")) as fh:
        manifest = json.load(fh)["machines"]
    names = watch.names
    in_window = list(range((opened + 1) * slice_size, (closed + 1) * slice_size))
    failed = [i for i in in_window if manifest.get(names[i], {}).get("status") != "completed"]
    committed = [i for i in in_window if i not in set(failed)]
    fetches = {f["machine"]: f for f in RECORDER.snapshot()}
    window_fetches = [
        f for f in fetches.values() if t_open <= f["at"] + f["seconds"] <= t_close
    ]

    deadline.enter("reducing the trace")
    # what the per-layer metrics' readers are given
    view: Dict[str, Any] = {
        "run": run, "compile_before": compile_before,
        "window_fetches": window_fetches,
        "memory_peak_bytes": device.get("memory_peak_bytes"),
        "platform": device["platform"], "trace": None, "peak": None,
        "counts": None,
    }
    breakdown = None
    if run["trace"]:
        view.update(trace_view(run, trace_data, trace_times, fetches, slice_size))
        breakdown = view.get("breakdown")
        if view["trace"] is not None:
            device["busy_s"] = view["trace"]["busy_s"]
            device["window_s"] = view["trace"]["window_s"]

    deadline.enter("check against the plain reference")
    checked = check(run, committed, out_dir, fetches) if committed else None
    # every number that decides ``correct``, each beside its limit
    judged = dict(checked["judged"]) if checked else {}
    for name, value in (("failed_machines", len(failed)),
                        ("window_compiles", compile_inside["cache_misses"])):
        judged[name] = {"value": float(value), "limit": 0, "ok": value == 0}
    correct = checked is not None and all(entry["ok"] for entry in judged.values())
    if failed:
        log(f"FAILED RUN: {len(failed)} of {len(in_window)} machines of the window did "
            f"not complete: {[names[i] for i in failed[:8]]}")
    if compile_inside["cache_misses"]:
        log(f"FAILED RUN: {compile_inside['cache_misses']} program(s) compiled inside the window")
    if checked is None:
        log("FAILED RUN: no machine of the window completed, so nothing was compared")
    values = {
        "machines_per_hour": machines / window_s * 3600.0,
        "setup_s": setup_s,
    }
    return {
        "correct": correct, "attempted": len(in_window), "failed": len(failed),
        "values": values, "view": view, "device": device,
        "breakdown": breakdown, "checked": checked, "judged": judged,
        "window_s": window_s,
    }


def first_slice_seconds(watch: "Watch", meter) -> float:
    """Seconds the job's first slice took once its program was there: from
    the end of the longest compile or cache load before its commit (the
    fleet program's: tracing and lowering come before it; ingest, the
    program and the commit after) to that commit; 0.0 where there is none."""
    done = watch.commits[0]
    loads = [e for e in list(meter.events) if e["at"] <= done]
    if not loads:
        return 0.0
    return done - max(loads, key=lambda e: e["seconds"])["at"]


def early_end(run, job, watch, where: str):
    job.thread.join(timeout=5.0)
    log(
        f"the job ended {where} (outcome {job.outcome!r}, {len(watch.commits)} "
        f"slice(s) committed): no rate is reported over a short window. Raise "
        f"fleet_machines in the configuration's file (now {sizes(run)[1]}) or "
        f"lower --seconds (now {run['seconds']})"
    )
    raise SystemExit(6)


def stop_trace_in_memory(trace_dir: str):
    """End the profiler's session and hand its trace back as ``ProfileData``
    without writing it. ``jax.profiler.stop_trace`` exports the planes and a
    ``trace.json.gz`` made from them: for the device events of one LSTM slice
    that was 262 MB of disk and 99 s, more than a run has (my chip run, PR
    24). The session is JAX's own object: a JAX that keeps it elsewhere ends
    the run here, by name, rather than with a slower call that cannot fit."""
    import jax

    try:
        from jax._src import profiler as jax_profiler

        state = jax_profiler._profile_state
        with state.lock:
            data = state.profile_session.stop_and_get_profile_data()
            state.reset()
    except (ImportError, AttributeError) as exc:
        log(
            f"jax {jax.__version__} keeps no profiler session at "
            f"jax._src.profiler._profile_state ({exc}); a traced run needs "
            f"stop_trace_in_memory rewritten for it (nothing written to {trace_dir})"
        )
        raise SystemExit(7)
    return data


def trace_view(run, trace_data, trace_times, fetches, slice_size) -> Dict[str, Any]:
    """The traced stretch, reduced: device numbers on the trace's clock, and
    each long idle gap named by the program's own span it falls in. Where
    the trace does not hold the stretch, stderr says what is missing and
    no device number is read off it."""
    from benchmarks import flops_bytes, trace_reduce
    from benchmarks.layer_metrics import execute_wait_s_per_slice

    if trace_data is None or run["device"]["platform"] == "cpu":
        return {"trace": None}
    started = time.perf_counter()
    reduced = trace_reduce.reduce_data(trace_data)
    sync = reduced["marks"].get("bench:sync")
    if not reduced["devices"] or not sync:
        log("trace: no device plane or no sync mark; planes unread")
        return {"trace": None}
    # the stretch begins at the sync mark, on the trace's own clock
    lo = sync[0][0]
    hi = lo + trace_times["stretch_s"]
    summary = trace_reduce.window_summary(reduced, lo, hi, trace_times["periods"])
    log(
        f"trace reduced in "
        f"{time.perf_counter() - started:.1f}s: {summary['chips']} chip(s), stretch "
        f"{summary['window_s']:.3f}s, busy {summary['busy_s']:.3f}s, programs' seconds "
        f"inside { {m: round(s, 3) for m, s in summary['module_s'].items() if s >= 1e-3} }, "
        f"whole runs { {m: len(r) for m, r in summary['modules'].items()} }"
    )
    short_by = trace_times["stretch_s"] - trace_times["ran_s"]
    lacks = (
        f"the trace stopped {short_by:.3f}s before the stretch's end" if short_by > 0
        else trace_reduce.missing(
            summary, run["config"].get("train_module"),
            execute_wait_s_per_slice.read({}),
        )
    )
    if lacks:
        log(f"trace: {lacks}; no device number is read off it")
        return {"trace": None}
    peak = harness.peak_for(run["device"]["kind"])
    counts = flops_bytes.slice_counts(
        run["config"]["reference_model"], slice_size, padded_rows(fetches),
        run["config"]["tags"],
    )
    named = trace_reduce.name_gaps(summary["gaps"], reduced["marks"])
    breakdown = {
        "device_ops": [[name, seconds] for name, seconds in summary["top_ops"]],
        "idle_gaps": [
            [name, seconds]
            for name, seconds in sorted(named.items(), key=lambda kv: -kv[1])[:10]
        ],
    }
    return {"trace": summary, "peak": peak, "counts": counts, "breakdown": breakdown}
