"""Compile a training cell's scanned train step for the v5e without a chip.

    JAX_PLATFORMS=cpu python benchmark/tools/compile_for_chip.py <cell>
        [--text out.hlo]

libtpu compiles ahead of time for a described topology (``v5e:2x2``), so
what the chip's compiler would refuse — a program that does not fit, a
sharding it cannot partition — is found before any chip time is spent.
The cell's workflow is built at its real widths on the CPU over the
driver's one-image stub; the program is lowered against shapes of the
cell's real data set placed on the described devices (one device, or a ``data`` mesh of
four).  Prints the compiler's memory analysis and how many fusions of each
kind the trace reduction would count as convolution- or dot-rooted, then
compiles what ``correct`` adds on the chip: ``jax.grad`` of the plain
reference's loss in float32 on one chip's batch.  Nothing runs: no number
printed here is a measurement.
"""

from __future__ import annotations

import argparse
import importlib
import os
import re
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def fusions_by_kind(text: str) -> dict:
    """``{kind: [fusions holding a convolution or dot, fusions without]}``
    of a compiled module's text, by the reduction's own table."""
    from benchmark.reduce import xplane

    table = xplane.fusion_table([text])
    counts = {}
    for line in text.splitlines():
        instr, opcode, kind, _, calls = xplane.parse_op(line.strip())
        if opcode == "fusion" and " = " in line:
            counts.setdefault(kind, [0, 0])[not table[(instr, calls)]] += 1
    return counts


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("cell")
    parser.add_argument("--text", default="")
    args = parser.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from benchmark import spec
    from znicz_tpu.backends import cache_dir
    from znicz_tpu.core import prng
    from znicz_tpu.core.config import root
    from znicz_tpu.parallel.fused import FusedTrainer

    jax.config.update("jax_enable_compilation_cache", False)
    cell = spec.Cell(spec.load(), args.cell)
    cfg, driver = cell.config, cell.driver()
    prng.seed_all(1)
    driver.apply_overrides(root, cfg["root"])
    size = int(cfg["root"][cfg["data"]["size_key"]])
    # as the driver builds it: the one-image stub stands in for the data
    # set, whose real size the configured class lengths give
    stub = os.path.join(cache_dir(), "bench", f"stub_{size}.npz")
    driver.stub_dataset(stub, size)
    driver.apply_overrides(root, {cfg["data"]["path_key"]: stub})
    wf = getattr(importlib.import_module(cfg["sample"]), cfg["workflow"])()
    wf.initialize(device=None)
    total = sum(wf.loader.class_lengths)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    if cell.chips == 1:
        mesh, place = None, SingleDeviceSharding(topo.devices[0])
    else:
        mesh = Mesh(np.array(topo.devices).reshape(cell.chips, 1),
                    ("data", "model"))
        place = NamedSharding(mesh, PartitionSpec())
    trainer = FusedTrainer(wf, mesh=mesh)
    scan = trainer.make_train_scan()
    steps = trainer.scan_chunk
    batch = int(wf.loader.max_minibatch_size)

    def shape(x, dtype=None):
        x = np.asarray(x) if not hasattr(x, "shape") else x
        return jax.ShapeDtypeStruct(x.shape, dtype or x.dtype,
                                    sharding=place)

    def row(*dims, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=place)

    tree = jax.tree_util.tree_map
    compiled = scan.lower(
        tree(shape, trainer.extract_params()),
        tree(shape, trainer.extract_velocities()),
        tree(shape, trainer.tiled_hypers(steps)),
        row(total, size, size, 3, dtype=jnp.float32), row(total),
        row(steps, batch), row(steps),
        shape(prng.get("fused_trainer").jax_base_key()),
        row(steps)).compile()
    text = compiled.as_text()
    if args.text:
        with open(args.text, "w") as f:
            f.write(text)
    print(f"{args.cell}: {steps}-step train scan at batch {batch} over "
          f"{total} resident images compiles for v5e:2x2 on "
          f"{cell.chips} device(s)")
    print("memory analysis (per device):", compiled.memory_analysis())
    for kind, (holds, without) in sorted(fusions_by_kind(text).items()):
        print(f"  {kind}: {holds} fusions hold a convolution or dot, "
              f"{without} do not")
    collectives = sorted(set(re.findall(
        r" (all-reduce[\w\-]*|all-gather[\w\-]*|reduce-scatter[\w\-]*)\(",
        text)))
    print("  collectives:", collectives or "none")

    params = trainer.extract_params()
    layers = [(shape(params[f.name]["weights"]),
               shape(params[f.name]["bias"]))
              for f in wf.forwards if f.has_weights]
    rows = int(cfg["architecture"]["batch_per_chip"])
    masks = [row(rows, *wf.forwards[i - 1].output_sample_shape,
                 dtype=jnp.float32)
             for i, f in enumerate(wf.forwards)
             if hasattr(f, "dropout_ratio")]
    grad = jax.jit(jax.grad(cell.reference().loss)).lower(
        layers, row(rows, size, size, 3, dtype=jnp.float32), row(rows),
        masks).compile()
    print(f"reference gradient (float32, {rows} rows) compiles; memory "
          f"analysis:", grad.memory_analysis())
    return 0


if __name__ == "__main__":
    sys.exit(main())
