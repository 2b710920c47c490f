#!/usr/bin/env python3
"""How much of a chip the plain reference's ``build`` asks for when one model
fills it: a synthetic kind that is nothing but parameters (a stack of
``--width``-wide products with a tanh between them, float32), through
``reference/build.py::make_build`` as any kind goes.

    JAX_PLATFORMS=cpu python benchmarks/tools/size_probe.py --describe 0.49e9,0.68e9
    python benchmarks/tools/size_probe.py --run 0.49e9      # on the chip

``--describe`` compiles for a described v5e (no chip attached: nothing runs)
and prints the compiler's ``memory_analysis()``; a program the chip cannot
hold is refused there by name. ``--run`` builds one machine of two optimizer
steps a fit on the chip JAX finds and prints the allocator's peak beside the
same analysis. Not run by the benchmark's own runs; ``PERF.md`` holds what it
read.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TAGS, BATCH, MICRO, N_SPLITS = 50, 8, 2, 2


def stack_kind(width: int) -> types.ModuleType:
    """A kind of ``layers`` square products: ``windows[:, 0] -> (B, n_out)``."""
    import jax
    import jax.numpy as jnp

    kind = types.ModuleType("benchmarks.reference.models.size_probe_stack")

    def init(model, key, n_features, n_out):
        k_in, k_out, *k_layers = jax.random.split(key, 2 + int(model["layers"]))
        return {
            "in": jax.random.normal(k_in, (n_features, width)) / jnp.sqrt(n_features),
            "out": jax.random.normal(k_out, (width, n_out)) / jnp.sqrt(width),
            # a leaf a layer, as a model's own reference has them
            **{f"layer_{i:03d}": jax.random.normal(k, (width, width)) / jnp.sqrt(width)
               for i, k in enumerate(k_layers)},
        }

    def apply(model, params, windows):
        h = jnp.tanh(windows[:, 0, :] @ params["in"])
        for i in range(int(model["layers"])):
            h = jnp.tanh(h @ params[f"layer_{i:03d}"])
        return h @ params["out"]

    def forward_flops(model, n_features):
        first = 2.0 * n_features * width
        return {"total": 2.0 * first + 2.0 * int(model["layers"]) * width * width,
                "first_layer": first}

    kind.layout = lambda model: (1, 0)
    kind.init, kind.apply, kind.forward_flops = init, apply, forward_flops
    kind.state_bytes = lambda model, n_features: 28.0 * n_parameters(model, width)
    sys.modules[kind.__name__] = kind
    return kind


def n_parameters(model, width: int) -> int:
    return int(model["layers"]) * width * width + 2 * TAGS * width


def probe(parameters: float, width: int, run: bool) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.reference import build as ref_build

    stack_kind(width)
    model = {
        "kind": "size_probe_stack", "layers": round(parameters / width**2),
        "epochs": 1, "batch_size": BATCH, "micro_batch": MICRO,
        "n_splits": N_SPLITS, "learning_rate": 1e-3,
    }
    n_rows = 2 * BATCH  # two optimizer steps a fit
    build, _, _ = ref_build.make_build(model, n_rows, TAGS)
    shapes = [((n_rows, TAGS), jnp.float32), ((n_rows,), jnp.float32), ((2,), jnp.uint32)]
    sharding = None
    if not run:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    out = {"parameters": n_parameters(model, width), "layers": model["layers"],
           "parameter_bytes": 4 * n_parameters(model, width), "n_splits": N_SPLITS}
    started = time.perf_counter()
    with jax.default_matmul_precision("highest"):
        try:
            compiled = jax.jit(build).lower(
                *[jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
            ).compile()
        except Exception as exc:  # the compiler's own refusal, kept as it reads
            out["refused"] = str(exc).strip().splitlines()[-1][:400]
            return out
        out["compile_s"] = time.perf_counter() - started
        memory = compiled.memory_analysis()
        for name in ("argument", "output", "temp", "alias", "generated_code"):
            out[f"{name}_bytes"] = int(getattr(memory, f"{name}_size_in_bytes"))
        out["copies"] = (out["output_bytes"] + out["temp_bytes"]) / out["parameter_bytes"]
        if run:
            rng = np.random.default_rng(0)
            X = rng.normal(size=shapes[0][0]).astype(np.float32)
            started = time.perf_counter()
            result = compiled(X, np.ones(shapes[1][0], np.float32), np.asarray(jax.random.PRNGKey(0)))
            out["loss_history"] = [float(v) for v in jax.device_get(result["loss_history"])]
            out["build_s"] = time.perf_counter() - started
            stats = jax.local_devices()[0].memory_stats() or {}
            out["device"] = jax.devices()[0].device_kind
            out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
            out["bytes_limit"] = stats.get("bytes_limit")
    return out


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    group = parser.add_mutually_exclusive_group(required=True)
    group.add_argument("--describe", help="parameter counts, comma-separated")
    group.add_argument("--run", help="parameter counts, comma-separated")
    parser.add_argument("--width", type=int, default=2048)
    args = parser.parse_args()
    if args.describe:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for parameters in (args.describe or args.run).split(","):
        print(json.dumps(probe(float(parameters), args.width, bool(args.run))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
