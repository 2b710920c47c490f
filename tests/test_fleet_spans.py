"""The fleet build's span timeline (ARCHITECTURE §13): one ``Timeline`` a
``build_fleet`` call, one span per phase of every slice, recorded by the
thread that does the work; handed to the flight recorder however the job
ends; the same names as ``TraceAnnotation`` marks in a profiler session;
and the benchmark's readers of those spans. Entered through the CLI, the
timeline begins at the command's entry, so that its set-up is on it too.

One small fleet (6 machines, slices of 2) is built once through the CLI
with ``--trace-dir`` and shared by the tests that only read it."""

import glob
import importlib
import json
import logging
import os
import time

import numpy as np
import pytest
import yaml

from gordo_components_tpu.cli import gordo
from gordo_components_tpu.dataset.dataset import RandomDataset
from gordo_components_tpu.observability import flightrec, tracing
from gordo_components_tpu.observability.registry import REGISTRY
from gordo_components_tpu.observability.spans import Timeline
from gordo_components_tpu.parallel import FleetMachineConfig, build_fleet
from gordo_components_tpu.observability.flightrec import TIMELINE_FILE

MODEL = {
    "DiffBasedAnomalyDetector": {
        "base_estimator": {
            "TransformedTargetRegressor": {
                "regressor": {
                    "Pipeline": {
                        "steps": [
                            "MinMaxScaler",
                            {"DenseAutoEncoder": {
                                "kind": "feedforward_symmetric",
                                "dims": [4], "epochs": 1, "batch_size": 32}},
                        ]
                    }
                },
                "transformer": "MinMaxScaler",
            }
        }
    }
}
DATASET = {
    "type": "RandomDataset",
    "train_start_date": "2023-01-01T00:00:00+00:00",
    "train_end_date": "2023-01-03T00:00:00+00:00",
}
N_MACHINES, SLICE = 6, 2
# a stand-in for the harness's process start, on the timeline's clock
STARTED = time.perf_counter()

# span -> (its parent's name, the thread that records it); the names are the
# contract the benchmark's readers and PERF.md use
MAIN, PREFETCH, POOL = "MainThread", "fleet-prefetch", "fleet-fetch"
COMMIT = "fleet-commit"  # slice s commits there while slice s+1 trains
SPAN_TREE = {
    "fleet.command": (None, MAIN),
    "fleet.config": ("fleet.command", MAIN),
    "fleet.mesh": ("fleet.command", MAIN),
    "fleet.job": (None, MAIN),
    "fleet.preamble": ("fleet.job", MAIN),
    "fleet.bucket": ("fleet.job", MAIN),
    "fleet.plan": ("fleet.bucket", MAIN),
    "fleet.slice": ("fleet.bucket", MAIN),
    "fleet.prefetch_wait": ("fleet.slice", MAIN),
    "fleet.ingest": ("fleet.slice", MAIN),
    "fleet.checkpoint_restore": ("fleet.slice", MAIN),
    "fleet.program": ("fleet.slice", MAIN),
    "fleet.trace": ("fleet.program", MAIN),
    "fleet.lower": ("fleet.program", MAIN),
    "fleet.compile": ("fleet.program", MAIN),
    "fleet.execute": ("fleet.slice", MAIN),
    "fleet.result_fetch": ("fleet.slice", MAIN),
    "fleet.checkpoint_save": ("fleet.slice", MAIN),
    "fleet.commit_wait": ("fleet.slice", MAIN),
    "fleet.commit_loop": ("fleet.slice", COMMIT),
    "fleet.commit": ("fleet.commit_loop", COMMIT),
    "fleet.manifest": ("fleet.slice", COMMIT),
    "fleet.checkpoint_wait": ("fleet.slice", MAIN),
    "fleet.prepare": ("fleet.bucket", PREFETCH),
    "fleet.fetch": ("fleet.prepare", POOL),
    "fleet.assemble": ("fleet.prepare", PREFETCH),
    "fleet.place": ("fleet.prepare", PREFETCH),
}
# the steady slice's readers, and the set-up's
SLICE_READERS = (
    "prefetch_wait_s_per_slice", "ingest_s_per_slice",
    "result_fetch_s_per_slice", "checkpoint_s_per_slice",
    "commit_s_per_machine", "execute_wait_s_per_slice",
    "slice_unattributed_s", "commit_wait_s_per_slice",
)
SETUP_READERS = (
    "setup_before_command_s", "setup_host_s", "first_fetch_wait_s",
    "program_trace_lower_s", "program_load_s", "setup_unattributed_s",
)
READERS = SLICE_READERS + SETUP_READERS
# what the loop's thread does in a slice: the readers of these cover it
LOOP_READERS = tuple(r for r in SLICE_READERS if r != "commit_s_per_machine")
# the spans of the set-up; a program from before them records none
SETUP_SPANS = (
    "fleet.command", "fleet.config", "fleet.mesh", "fleet.plan",
    "fleet.trace", "fleet.lower", "fleet.compile",
)
# what the harness hands a reader: its process start among the rest
VIEW = {"run": {"started": STARTED}}


def _machines(prefix, dataset=None):
    return [
        {"name": f"{prefix}-{i}",
         "dataset": {"tag_list": [f"{prefix}{i}-a", f"{prefix}{i}-b"],
                     **(dataset or {})}}
        for i in range(N_MACHINES)
    ]


@pytest.fixture(scope="module")
def traced_build(tmp_path_factory):
    """``gordo fleet-build --trace-dir`` on the calling thread, the way the
    benchmark enters the program; returns the job's timeline and trace dir."""
    root = tmp_path_factory.mktemp("fleet-spans")
    config = root / "fleet.yaml"
    config.write_text(yaml.safe_dump({
        "project-name": "spans", "machines": _machines("sp"),
        "globals": {"model": MODEL, "dataset": DATASET},
    }))
    trace_dir = str(root / "trace")
    gordo.main(
        ["fleet-build", "--machine-config", str(config),
         "--output-dir", str(root / "models"), "--n-splits", "1",
         "--n-devices", "1", "--slice-size", str(SLICE),
         "--no-serving-cache", "--trace-dir", trace_dir],
        standalone_mode=False,
    )
    timeline = flightrec.RECORDER.latest(kind="fleet-build")
    assert timeline is not None and timeline.status == "ok"
    return timeline, trace_dir


@pytest.fixture
def recorder(monkeypatch):
    """A recorder of this test's own in the process-wide one's place."""
    fresh = flightrec.FlightRecorder(enabled=True)
    monkeypatch.setattr(flightrec, "RECORDER", fresh)
    return fresh


@pytest.mark.parametrize("name", sorted(SPAN_TREE))
def test_span_has_its_parent_and_thread(traced_build, name):
    timeline, _ = traced_build
    by_id = {span.id: span for span in timeline.spans}
    found = [span for span in timeline.spans if span.name == name]
    assert found, f"no {name} span recorded"
    parent_name, thread = SPAN_TREE[name]
    for span in found:
        assert span.thread.startswith(thread), (name, span.thread)
        if parent_name is None:
            assert span.parent == 0
        else:
            assert by_id[span.parent].name == parent_name
    per_slice = N_MACHINES // SLICE
    expected = {
        "fleet.job": 1, "fleet.slice": per_slice, "fleet.prepare": per_slice,
        "fleet.command": 1, "fleet.config": 1, "fleet.mesh": 1,
        "fleet.plan": 1,
        # the first slice's program only: the others are memo hits
        "fleet.trace": 1, "fleet.lower": 1, "fleet.compile": 1,
        "fleet.commit": N_MACHINES, "fleet.fetch": N_MACHINES,
        "fleet.ingest": 2 * per_slice,  # batch assembly, then the device_put
        "fleet.commit_loop": per_slice, "fleet.manifest": per_slice,
        # every slice's commit is joined once: by the slice after it, the
        # bucket's last by itself
        "fleet.commit_wait": per_slice, "fleet.checkpoint_wait": per_slice,
    }
    if name in expected:
        assert len(found) == expected[name]
    if name == "fleet.commit_wait":
        slices = {s.id: s.attrs["slice"] for s in timeline.spans
                  if s.name == "fleet.slice"}
        assert sorted(
            (slices[w.parent], w.attrs["slice"]) for w in found
        ) == [(1, 0), (2, 1), (2, 2)]


def test_fetch_span_carries_the_resample_path(traced_build):
    timeline, _ = traced_build
    fetches = [s for s in timeline.spans if s.name == "fleet.fetch"]
    # RandomDataset's float64 series on a UTC index: the numpy path
    assert [s.attrs["resample"] for s in fetches] == ["numpy"] * N_MACHINES


def test_slice_children_cover_it_to_within_its_self_time(traced_build):
    timeline, _ = traced_build
    self_seconds = timeline.self_seconds()
    slices = [s for s in timeline.spans if s.name == "fleet.slice"]
    for parent in slices:
        children = [s for s in timeline.spans if s.parent == parent.id]
        # the loop's thread, one phase after the other: no child overlaps
        # the next
        on_loop = sorted(
            (s for s in children if s.thread == parent.thread),
            key=lambda s: s.start,
        )
        for a, b in zip(on_loop, on_loop[1:]):
            assert a.start + a.duration <= b.start + 1e-6
        # the slice's commit runs on the worker, after the slice's span or
        # (the last slice) beside its wait for it: a child is clipped to its
        # parent's interval, so the self time stays the loop's own
        loop_self = parent.duration - sum(s.duration for s in on_loop)
        assert -1e-6 <= self_seconds[parent.id] <= loop_self + 1e-6
        # every phase of the loop has a span: what is left is the loop's own
        # few statements between them
        assert loop_self < 0.25 + 0.05 * parent.duration
        worker = [s for s in children if s.thread != parent.thread]
        assert sorted(s.name for s in worker) == [
            "fleet.commit_loop", "fleet.manifest"
        ]
        assert all(s.thread.startswith(COMMIT) for s in worker)
        # handed over once the slice's own checkpoint is on its way
        saved, = [s for s in on_loop if s.name == "fleet.checkpoint_save"]
        assert min(s.start for s in worker) >= saved.start + saved.duration
    # the first slice holds the compile, and says so
    programs = sorted(
        (s for s in timeline.spans if s.name == "fleet.program"),
        key=lambda s: s.start,
    )
    assert [p.attrs["memo_hit"] for p in programs] == [False, True, True]
    staged = [
        s for s in timeline.spans if s.parent == programs[0].id
    ]
    assert [s.name for s in sorted(staged, key=lambda s: s.start)] == [
        "fleet.trace", "fleet.lower", "fleet.compile"
    ]
    assert all(s.attrs["program"] == "train" for s in staged)
    assert staged[-1].attrs["cache"] in ("hit", "miss", "off")
    # the stages are the program's work: they fill its span but for the
    # avatars' shapes
    assert sum(s.duration for s in staged) > 0.5 * programs[0].duration


def test_trace_dir_holds_one_session_and_a_loadable_timeline(traced_build):
    timeline, trace_dir = traced_build
    with open(os.path.join(trace_dir, TIMELINE_FILE)) as fh:
        chrome = json.load(fh)
    complete = [e for e in chrome["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in complete} == set(SPAN_TREE)
    assert len(complete) == len(timeline.spans)
    assert chrome["otherData"]["trace_id"] == timeline.trace_id
    assert chrome["otherData"]["kind"] == "fleet-build"
    by_id = {e["args"]["id"]: e for e in complete}
    commit = next(e for e in complete if e["name"] == "fleet.commit")
    assert by_id[commit["args"]["parent"]]["name"] == "fleet.commit_loop"
    # one profiler session a job, not one a slice
    sessions = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*"))
    assert len(sessions) == 1
    assert len(glob.glob(os.path.join(sessions[0], "*.xplane.pb"))) == 1


def test_profiler_session_holds_the_spans_as_host_annotations(traced_build):
    """One clock: inside a profiler session every span of the build is a
    ``TraceAnnotation`` on the host plane of the xplane that holds the
    device's ops. The session is around the second slice, whole."""
    from jax.profiler import ProfileData

    timeline, trace_dir = traced_build
    (path,) = glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    )
    data = ProfileData.from_file(path)
    host = [plane for plane in data.planes if plane.name == "/host:CPU"]
    assert host, [plane.name for plane in data.planes]
    names = {}
    for line in host[0].lines:
        for event in line.events:
            if event.name.startswith("fleet."):
                names.setdefault(event.name, []).append(event.duration_ns)
    per_slice_phases = {
        name for name, (parent, _) in SPAN_TREE.items()
        if parent in ("fleet.slice", "fleet.commit_loop")
    } | {"fleet.slice"}
    assert per_slice_phases <= set(names)
    # the next slice's prefetch runs beside the traced one on the worker's
    # threads
    assert {"fleet.prepare", "fleet.fetch", "fleet.assemble"} <= set(names)
    ordered = sorted(
        (s for s in timeline.spans if s.name == "fleet.slice"),
        key=lambda s: s.start,
    )
    traced = ordered[1]
    # one session, open from the traced slice's start until its commit has
    # been joined (by the slice after it, which is in the session as far as
    # that): the traced slice whole ...
    assert len(names["fleet.slice"]) == 2

    def held(span):
        """An annotation of the span's name and length."""
        return any(
            ns * 1e-9 == pytest.approx(span.duration, rel=0.05, abs=0.005)
            for ns in names[span.name]
        )

    assert held(traced)
    # ... and its whole commit: the loop, one commit a machine inside, the
    # manifest, and the loop's wait for them
    commit_loop, = [
        s for s in timeline.spans
        if s.name == "fleet.commit_loop" and s.parent == traced.id
    ]
    commits = [s for s in timeline.spans if s.parent == commit_loop.id]
    assert [s.name for s in commits] == ["fleet.commit"] * SLICE
    assert held(commit_loop) and all(held(s) for s in commits)
    assert SLICE <= len(names["fleet.commit"]) <= N_MACHINES
    wait, = [
        s for s in timeline.spans
        if s.name == "fleet.commit_wait" and s.attrs["slice"] == 1
    ]
    assert wait.parent == ordered[2].id and held(wait)


class StopJob(BaseException):
    """What the benchmark ends a job with: not an ``Exception``, so the
    fetch's retry and isolation let it through."""


class StoppingDataset(RandomDataset):
    """Logs from the fetch pool's thread, and ends the job from the third
    slice's first fetch on."""

    log = logging.getLogger("test_fleet_spans.dataset")

    def __init__(self, *args, index=0, **kwargs):
        super().__init__(*args, **kwargs)
        self.index = index

    def get_data(self):
        self.log.info("fetching %d", self.index)
        if self.index >= 2 * SLICE:
            raise StopJob(self.index)
        return super().get_data()


@pytest.fixture(scope="module")
def stopped_build(tmp_path_factory):
    """A job ended by an exception raised from a dataset's ``get_data``."""
    tracing.install_log_record_factory()
    records = []
    handler = logging.Handler()
    handler.emit = records.append
    StoppingDataset.log.addHandler(handler)
    StoppingDataset.log.setLevel(logging.INFO)
    machines = [
        FleetMachineConfig(
            name=f"st-{i}", model_config=MODEL,
            data_config={**DATASET, "tag_list": [f"st{i}-a", f"st{i}-b"],
                         "type": f"{__name__}.StoppingDataset", "index": i},
        )
        for i in range(N_MACHINES)
    ]
    out = str(tmp_path_factory.mktemp("fleet-stopped"))
    try:
        with pytest.raises(StopJob):
            build_fleet(machines, out, n_splits=1, slice_size=SLICE)
    finally:
        StoppingDataset.log.removeHandler(handler)
    return flightrec.RECORDER.latest(kind="fleet-build"), records


def test_timeline_is_recorded_when_get_data_ends_the_job(stopped_build):
    timeline, _ = stopped_build
    assert timeline is not None and timeline.finished is not None
    assert timeline.status == "error" and timeline.error.startswith("StopJob")
    slices = sorted(
        (s for s in timeline.spans if s.name == "fleet.slice"),
        key=lambda s: s.start,
    )
    # two slices committed; the third ended in its wait for the prefetch
    assert ["error" in s.attrs for s in slices] == [False, False, True]
    waits = [s for s in timeline.spans if s.parent == slices[2].id]
    assert [(s.name, s.attrs.get("error")) for s in waits] == [
        ("fleet.prefetch_wait", "StopJob")
    ]
    (job,) = [s for s in timeline.spans if s.name == "fleet.job"]
    assert job.attrs["error"] == "StopJob"
    commits = [s for s in timeline.spans if s.name == "fleet.commit"]
    assert len(commits) == 2 * SLICE
    assert {s.attrs["outcome"] for s in commits} == {"completed"}


def test_worker_side_spans_and_logs_carry_the_jobs_trace_id(stopped_build):
    timeline, records = stopped_build
    assert len(timeline.trace_id) == 16
    assert len(records) >= 2 * SLICE + 1
    for record in records:
        assert record.threadName.startswith(("fleet-fetch", "fleet-prefetch"))
        assert record.trace_id == timeline.trace_id
    fetches = [s for s in timeline.spans if s.name == "fleet.fetch"]
    assert {s.attrs["machine"] for s in fetches} >= {
        f"st-{i}" for i in range(2 * SLICE)
    }
    # the job's id does not outlive the job on the calling thread
    assert tracing.get_trace_id() == ""


@pytest.mark.parametrize("name", READERS)
def test_reader_reads_the_steady_slices(traced_build, recorder, name):
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert reader.read(VIEW) is None  # an empty recorder: nothing to read
    recorder.record(traced_build[0])
    value = reader.read(VIEW)
    assert isinstance(value, float) and value >= 0.0


def test_vmapped_folds_count_no_predicted_samples(traced_build, recorder):
    """The vmapped folds predict only what their result reads, as the
    sequential ones do, and count it the same way: every slice carries
    ``predicted_samples`` of ``predictable_samples`` a machine, and
    ``predicted_samples_pct`` reads their share over the steady slices."""
    from benchmarks.layer_metrics import predicted_samples_pct, slice_spans

    recorder.record(traced_build[0])
    slices = [s for s in traced_build[0].spans if s.name == "fleet.slice"]
    assert slices
    for one in slices:
        predicted = np.asarray(one.attrs["predicted_samples"])
        predictable = np.asarray(one.attrs["predictable_samples"])
        assert predicted.shape == predictable.shape == (SLICE,)
        # the one fold (two fits of padded samples) predicts half the real
        # samples, the final fit none
        assert np.all(predicted > 0) and np.all(4 * predicted <= predictable)
    steady = slice_spans.steady()
    predicted = sum(np.sum(s["attrs"]["predicted_samples"]) for s in steady)
    predictable = sum(np.sum(s["attrs"]["predictable_samples"]) for s in steady)
    assert predicted_samples_pct.read({}) == pytest.approx(
        100.0 * predicted / predictable
    )


def test_readers_partition_the_steady_slice(traced_build, stopped_build, recorder):
    """What the loop's thread does in a steady slice is read whole: its
    phases, the wait for the worker's commit of the slice before among them,
    and its own statements between. The commit itself is the worker's."""
    from benchmarks.layer_metrics import fleet_spans

    def values():
        return {
            name: importlib.import_module(
                f"benchmarks.layer_metrics.{name}"
            ).read({})
            for name in SLICE_READERS
        }

    for timeline, steady_index in ((traced_build[0], 1), (stopped_build[0], 1)):
        recorder.clear()
        recorder.record(timeline)
        # three committed slices: the middle one; two: the second
        slices = sorted(
            (s for s in timeline.spans
             if s.name == "fleet.slice" and "error" not in s.attrs),
            key=lambda s: s.start,
        )
        (steady,) = fleet_spans.steady_slices()
        parent = slices[steady_index]
        assert steady["seconds"] == parent.duration
        read = values()
        per_machine = read.pop("commit_s_per_machine")
        assert set(read) == set(LOOP_READERS)
        on_loop = [
            s for s in timeline.spans
            if s.parent == parent.id and s.thread == parent.thread
        ]
        assert "fleet.commit_wait" in {s.name for s in on_loop}
        assert sum(read.values()) == pytest.approx(
            sum(s.duration for s in on_loop) + steady["self_s"], abs=1e-9
        )
        # the worker's commit begins as the slice's span ends: the sliver of
        # it inside the span is all that the sum can miss of the slice
        assert steady["seconds"] - 0.05 <= sum(read.values()) <= (
            steady["seconds"] + 1e-9
        )
        # the commit is read off the worker's spans, under the same slice
        commit = [
            s for s in timeline.spans
            if s.parent == parent.id and s.thread.startswith(COMMIT)
        ]
        assert SLICE * per_machine == pytest.approx(
            sum(s.duration for s in commit), abs=1e-9
        )


@pytest.mark.parametrize("name", ("fleet.trace", "fleet.lower", "fleet.compile"))
def test_memo_hit_slice_records_no_program_stage(traced_build, name):
    """A steady slice finds its executable in the memo: it traces, lowers
    and compiles nothing, and its spans are the same as before the split."""
    timeline, _ = traced_build
    by_id = {span.id: span for span in timeline.spans}
    programs = [s for s in timeline.spans if s.name == "fleet.program"]
    staged = [s for s in timeline.spans if s.name == name]
    assert [by_id[s.parent].attrs["memo_hit"] for s in staged] == [False]
    for program in programs:
        if program.attrs["memo_hit"]:
            assert not [s for s in timeline.spans if s.parent == program.id]


def test_compile_span_says_what_the_persistent_cache_answered(
    tmp_path, monkeypatch, recorder
):
    """Two jobs of the same program, each with an empty program memo, over
    one empty persistent cache that keeps every program: the first compiles
    and writes it, the second loads it."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from gordo_components_tpu.parallel import fleet

    machines = [
        FleetMachineConfig(
            name=f"cc-{i}", model_config=MODEL,
            data_config={**DATASET, "tag_list": [f"cc{i}-a", f"cc{i}-b"]},
        )
        for i in range(SLICE)
    ]
    was = (
        jax.config.jax_compilation_cache_dir,
        jax.config.jax_persistent_cache_min_compile_time_secs,
    )
    built = REGISTRY.counter(
        "gordo_fleet_programs_built_total", labels=("kind", "cache")
    )
    before = dict(built.collect())
    answers = []
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path / "cache"))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        for job in range(2):
            compilation_cache.reset_cache()
            for memo in ("_PROGRAM_CACHE", "_STATE_CACHE", "_EXEC_CACHE"):
                monkeypatch.setattr(fleet, memo, {})
            build_fleet(
                machines, str(tmp_path / f"out-{job}"), n_splits=1,
                slice_size=SLICE,
            )
            timeline = recorder.latest(kind="fleet-build")
            answers.append([
                s.attrs["cache"] for s in timeline.spans
                if s.name == "fleet.compile" and s.attrs["program"] == "train"
            ])
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", was[1]
        )
        compilation_cache.reset_cache()
    assert answers == [["miss"], ["hit"]]
    # the counter at the same boundary: one train executable each way
    after = built.collect()
    for cache in ("miss", "hit"):
        key = ("aot", cache)
        assert after.get(key, 0) - before.get(key, 0) == 1, key


def _set_up_end(timeline):
    """The end of the first slice's commit loop, on the timeline's clock."""
    (first,) = [
        s for s in timeline.spans
        if s.name == "fleet.slice" and s.attrs["slice"] == 0
    ]
    (loop,) = [
        s for s in timeline.spans
        if s.parent == first.id and s.name == "fleet.commit_loop"
    ]
    return first, loop.start + loop.duration


def test_setup_readers_add_up_to_the_set_up(traced_build, recorder):
    """From the process's start to the first slice's commit: the time
    before the command, the union of the set-up's measured spans and what
    none of them covers add up to the whole; and the readers read the
    spans they name."""
    timeline, _ = traced_build
    recorder.record(timeline)
    read = {
        name: importlib.import_module(
            f"benchmarks.layer_metrics.{name}"
        ).read(VIEW)
        for name in SETUP_READERS
    }
    first, end = _set_up_end(timeline)
    (command,) = [s for s in timeline.spans if s.name == "fleet.command"]
    listed = [
        s for s in timeline.spans
        if s.name in ("fleet.command", "fleet.preamble", "fleet.plan")
        or (s.parent == first.id and s.name != "fleet.manifest")
    ]
    union, edge = 0.0, command.start
    for s in sorted(listed, key=lambda s: s.start):
        lo, hi = max(s.start, edge), min(s.start + s.duration, end)
        if hi > lo:
            union, edge = union + hi - lo, hi
    assert read["setup_before_command_s"] + union + read[
        "setup_unattributed_s"
    ] == pytest.approx(timeline.started + end - STARTED, abs=1e-3)
    assert read["setup_before_command_s"] == pytest.approx(
        timeline.started + command.start - STARTED, abs=1e-9
    )

    def seconds(*names, parent=None):
        return sum(
            s.duration for s in timeline.spans if s.name in names
            and (parent is None or s.parent == parent)
        )

    assert read["setup_host_s"] == pytest.approx(
        seconds("fleet.command", "fleet.preamble", "fleet.plan"), abs=1e-9
    )
    assert read["first_fetch_wait_s"] == pytest.approx(
        seconds("fleet.prefetch_wait", parent=first.id), abs=1e-9
    )
    assert read["program_trace_lower_s"] == pytest.approx(
        seconds("fleet.trace", "fleet.lower"), abs=1e-9
    )
    assert read["program_load_s"] == pytest.approx(
        seconds("fleet.compile"), abs=1e-9
    )
    # every phase of the set-up has a span: what is left is the loop's own
    # few statements between them
    assert read["setup_unattributed_s"] < 0.25 + 0.05 * (end - command.start)


@pytest.mark.parametrize("name", SETUP_READERS)
def test_setup_reader_reads_nothing_without_the_set_up_spans(
    traced_build, recorder, name
):
    """A program from before the set-up's spans: its timeline has every
    other span, and the reader gives nothing rather than a wrong number."""
    timeline, _ = traced_build
    older = Timeline(timeline.trace_id, **timeline.meta)
    older.started = timeline.started
    older.spans = [s for s in timeline.spans if s.name not in SETUP_SPANS]
    older.finish(status="ok")
    recorder.record(older)
    reader = importlib.import_module(f"benchmarks.layer_metrics.{name}")
    assert reader.read(VIEW) is None
