"""The fleet build's span timeline (ARCHITECTURE §13): one ``Timeline`` a
``build_fleet`` call, one span per phase of every slice, recorded by the
thread that does the work; handed to the flight recorder however the job
ends; the same names as ``TraceAnnotation`` marks in a profiler session;
and the benchmark's readers of those spans.

One small fleet (6 machines, slices of 2) is built once through the CLI
with ``--trace-dir`` and shared by the tests that only read it."""

import glob
import importlib
import json
import logging
import os

import pytest
import yaml

from gordo_components_tpu.cli import gordo
from gordo_components_tpu.dataset.dataset import RandomDataset
from gordo_components_tpu.observability import flightrec, tracing
from gordo_components_tpu.parallel import FleetMachineConfig, build_fleet
from gordo_components_tpu.parallel.build_fleet import TIMELINE_FILE

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

# span -> (its parent's name, the thread that records it); the names are the
# contract the benchmark's readers and PERF.md use
MAIN, PREFETCH, POOL = "MainThread", "fleet-prefetch", "fleet-fetch"
SPAN_TREE = {
    "fleet.job": (None, MAIN),
    "fleet.preamble": ("fleet.job", MAIN),
    "fleet.bucket": ("fleet.job", MAIN),
    "fleet.slice": ("fleet.bucket", MAIN),
    "fleet.prefetch_wait": ("fleet.slice", MAIN),
    "fleet.ingest": ("fleet.slice", MAIN),
    "fleet.checkpoint_restore": ("fleet.slice", MAIN),
    "fleet.program": ("fleet.slice", MAIN),
    "fleet.execute": ("fleet.slice", MAIN),
    "fleet.result_fetch": ("fleet.slice", MAIN),
    "fleet.checkpoint_save": ("fleet.slice", MAIN),
    "fleet.commit_loop": ("fleet.slice", MAIN),
    "fleet.commit": ("fleet.commit_loop", MAIN),
    "fleet.manifest": ("fleet.slice", MAIN),
    "fleet.checkpoint_wait": ("fleet.slice", MAIN),
    "fleet.prepare": ("fleet.bucket", PREFETCH),
    "fleet.fetch": ("fleet.prepare", POOL),
    "fleet.assemble": ("fleet.prepare", PREFETCH),
    "fleet.place": ("fleet.prepare", PREFETCH),
}
READERS = (
    "prefetch_wait_s_per_slice", "ingest_s_per_slice",
    "result_fetch_s_per_slice", "checkpoint_s_per_slice",
    "commit_s_per_machine", "execute_wait_s_per_slice",
    "slice_unattributed_s",
)


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
        "fleet.commit": N_MACHINES, "fleet.fetch": N_MACHINES,
        "fleet.ingest": 2 * per_slice,  # batch assembly, then the device_put
    }
    if name in expected:
        assert len(found) == expected[name]


def test_slice_children_cover_it_to_within_its_self_time(traced_build):
    timeline, _ = traced_build
    self_seconds = timeline.self_seconds()
    slices = [s for s in timeline.spans if s.name == "fleet.slice"]
    for parent in slices:
        children = [s for s in timeline.spans if s.parent == parent.id]
        # one thread, one after the other: no child overlaps the next
        ordered = sorted(children, key=lambda s: s.start)
        for a, b in zip(ordered, ordered[1:]):
            assert a.start + a.duration <= b.start + 1e-6
        covered = sum(s.duration for s in children)
        assert covered + self_seconds[parent.id] == pytest.approx(
            parent.duration, abs=1e-6
        )
        # every phase of the loop has a span: what is left is the loop's own
        # few statements between them
        assert self_seconds[parent.id] < 0.25 + 0.05 * parent.duration
    # the first slice holds the compile, and says so
    programs = sorted(
        (s for s in timeline.spans if s.name == "fleet.program"),
        key=lambda s: s.start,
    )
    assert [p.attrs["memo_hit"] for p in programs] == [False, True, True]
    assert programs[0].attrs["compile_s"] > 0 == programs[1].attrs["compile_s"]


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
    # the one traced slice, not all three; and the next slice's prefetch,
    # which runs beside it on the worker's threads
    assert len(names["fleet.slice"]) == 1
    assert len(names["fleet.commit"]) == SLICE
    assert {"fleet.prepare", "fleet.fetch", "fleet.assemble"} <= set(names)
    traced = sorted(
        (s for s in timeline.spans if s.name == "fleet.slice"),
        key=lambda s: s.start,
    )[1]
    assert names["fleet.slice"][0] * 1e-9 == pytest.approx(
        traced.duration, rel=0.05, abs=0.005
    )


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
    assert reader.read({}) is None  # an empty recorder: nothing to read
    recorder.record(traced_build[0])
    value = reader.read({})
    assert isinstance(value, float) and value >= 0.0


def test_readers_partition_the_steady_slice(traced_build, stopped_build, recorder):
    from benchmarks.layer_metrics import fleet_spans

    def values():
        return {
            name: importlib.import_module(
                f"benchmarks.layer_metrics.{name}"
            ).read({})
            for name in READERS
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
        assert steady["seconds"] == slices[steady_index].duration
        read = values()
        per_machine = read.pop("commit_s_per_machine")
        assert sum(read.values()) + SLICE * per_machine == pytest.approx(
            steady["seconds"], abs=1e-9
        )
