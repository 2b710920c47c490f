"""The plain reference and ``slice_counts`` give, for the kinds that were
there before a kind could bring a loss, a layout and blocks of its own
(``lstm``, ``dense``), what they gave then: every key of ``make_build``'s
result, ``anomaly``'s score and ``slice_counts``' four numbers, compared with
``golden/<kind>.npz``, recorded on the CPU from the tree before that change
(commit 6736d58). A leaf of over ``WHOLE`` numbers is kept as ``summary``
has it (a digest of its bytes, four moments, sixteen of its numbers), not
whole. ``slice_counts``' numbers and every digest are compared exactly; a
float key that differs (another host's vector units, another XLA: the
recording host's own read all equal) is held to ``FALLBACK`` relative and the
test says which keys needed it.

Run by hand: ``JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q``.
Record anew (only from a tree whose reference is known to be right):
``JAX_PLATFORMS=cpu python -m benchmarks.tests.test_reference_golden``.
"""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CASES = {"lstm": "lstm-ae-50tag", "dense": "dense-ae-10tag"}
# rehearsal size: two weeks of 10-minute rows, padded as the program pads
N_ROWS, N_REAL, PROBE_ROWS, SLICE = 2048, 2016, 64, 4


def _leaves(path, tree):
    if not isinstance(tree, dict):
        yield path, tree
        return
    for key in sorted(tree):
        yield from _leaves(f"{path}/{key}", tree[key])


def golden_case(kind):
    """``{key: array}`` of one machine's reference build at rehearsal size,
    from the configuration's own ``reference_model``."""
    import jax

    from benchmarks import flops_bytes
    from benchmarks.reference import build as ref_build

    with open(os.path.join(BENCH, "configs", f"{CASES[kind]}.json")) as fh:
        config = json.load(fh)
    model, tags = config["reference_model"], config["tags"]
    rng = np.random.default_rng(20260929)
    raw = np.cumsum(rng.normal(size=(N_REAL, tags)), axis=0).astype(np.float32)
    X = np.zeros((N_ROWS, tags), np.float32)
    w = np.zeros((N_ROWS,), np.float32)
    X[N_ROWS - N_REAL:], w[N_ROWS - N_REAL:] = raw, 1.0
    build, anomaly, initial = ref_build.make_build(model, N_ROWS, tags)

    def one(X, w, key, probe):
        result = build(X, w, key)
        result["anomaly_mean"] = anomaly(result, probe)
        result["params0"] = initial(key)  # a key of ``build``'s own result then
        return result

    with jax.default_matmul_precision("highest"):
        out = jax.device_get(jax.jit(one)(X, w, jax.random.PRNGKey(7), raw[-PROBE_ROWS:]))
    flat = dict(_leaves("params0", out.pop("params0")))
    flat.update(_leaves("params", out.pop("params")))
    flat.update(out)
    counts = flops_bytes.slice_counts(model, SLICE, N_ROWS, tags)
    flat.update({f"slice_counts/{k}": np.float64(v) for k, v in counts.items()})
    return {k: np.asarray(v) for k, v in flat.items()}


WHOLE, FALLBACK = 64, 1e-6


def summary(key, leaf):
    """What the golden file keeps of one key: the leaf itself where it is
    small, else a digest of its bytes, its sum, norm, least and largest in
    float64, and sixteen of its numbers evenly spread."""
    leaf = np.ascontiguousarray(leaf)
    if leaf.size <= WHOLE:
        return {key: leaf}
    import hashlib

    wide = leaf.astype(np.float64).ravel()
    return {
        f"{key}#digest": np.frombuffer(hashlib.sha256(leaf.tobytes()).digest(), np.uint8),
        f"{key}#moments": np.array([wide.sum(), np.linalg.norm(wide), wide.min(), wide.max()]),
        f"{key}#sample": leaf.ravel()[:: leaf.size // 16][:16],
    }


def summarised(case):
    out = {}
    for key, leaf in case.items():
        out.update(summary(key, leaf))
    return out


@pytest.mark.parametrize("kind", sorted(CASES))
def test_the_reference_reads_what_it_read_before_kinds_could_bring_more(kind):
    with np.load(os.path.join(HERE, "golden", f"{kind}.npz")) as stored:
        golden = {k: stored[k] for k in stored.files}
    now = summarised(golden_case(kind))
    assert sorted(now) == sorted(golden)
    assert sum(k.startswith("slice_counts/") for k in now) == 4
    needed = {}
    for key, was in golden.items():
        value = np.asarray(now[key], was.dtype)
        assert value.shape == was.shape, key
        if np.array_equal(value, was):
            continue
        leaf = key.split("#")[0]
        assert not key.startswith("slice_counts/"), (key, value, was)
        if key.endswith("#digest"):  # the leaf differs: its moments and sample say by how much
            needed.setdefault(leaf, 0.0)
            continue
        assert was.dtype.kind == "f", (key, value, was)
        # a sample against its leaf's largest number, anything else its own
        scale = np.max(np.abs(golden[f"{leaf}#moments"][-2:] if key.endswith("#sample") else was))
        apart = float(np.max(np.abs(value.astype(np.float64) - was)) / max(scale, 1e-30))
        assert apart <= FALLBACK, (key, apart)
        needed[leaf] = max(needed.get(leaf, 0.0), apart)
    if needed:
        import warnings

        warnings.warn(f"{kind}: not equal to the bit, within {FALLBACK} relative: {needed}")


if __name__ == "__main__":
    os.makedirs(os.path.join(HERE, "golden"), exist_ok=True)
    for kind_ in sorted(CASES):
        case = summarised(golden_case(kind_))
        np.savez_compressed(os.path.join(HERE, "golden", f"{kind_}.npz"), **case)
        print(kind_, len(case), "keys", {k: case[k].tolist() for k in case if case[k].ndim == 0})
