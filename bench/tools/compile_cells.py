"""Compile each cell's device programs for a described TPU v5e chip, with
no chip attached, and print what the compiler says they need.

    JAX_PLATFORMS=cpu PYTHONPATH=src python bench/tools/compile_cells.py \
        [cell ...]

For each training cell: the jitted train step at the configuration's
depth and the traffic's batch, with the argument, output and temporary
bytes of ``memory_analysis()``.  A compile
that passes is not a chip run: it says nothing of times or results.
"""
from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def _mem(label, compiled):
    m = compiled.memory_analysis()
    gb = 1e-9
    print(f"{label}: args {m.argument_size_in_bytes * gb:.3f} GB, "
          f"outputs {m.output_size_in_bytes * gb:.3f} GB, "
          f"temps {m.temp_size_in_bytes * gb:.3f} GB, "
          f"aliased {m.alias_size_in_bytes * gb:.3f} GB", flush=True)


def _shapes(tree, sharding):
    import jax
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=sharding), tree)


def train_cell(ctx, one):
    import jax

    from bench.drivers.train import model_config
    from repro.train import init_state, make_train_step
    conf, traffic = ctx["config"], ctx["traffic"]
    cfg = model_config(conf)
    opt = traffic["optimizer"]
    step = jax.jit(make_train_step(
        cfg, peak_lr=opt["peak_lr"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
        clip_norm=opt["clip_norm"]))
    state = _shapes(jax.eval_shape(
        lambda: init_state(cfg, jax.random.PRNGKey(0))), one)
    rows, seq = traffic["rows_per_data_replica"], traffic["seq_len"]
    batch = _shapes({"tokens": jax.ShapeDtypeStruct((rows, seq), "int32"),
                     "targets": jax.ShapeDtypeStruct((rows, seq), "int32")},
                    one)
    _mem(f"{conf['name']} train step", step.lower(state, batch).compile())


def main(argv):
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.run import cell_context, manifest
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    bench = manifest()
    for name in argv or [w["name"] for w in bench["workloads"]]:
        ctx = cell_context(name, bench)
        if ctx["cell"]["chips"] != 1:
            print(f"{name}: skipped, a {ctx['cell']['chips']}-chip cell")
            continue
        train_cell(ctx, one)


if __name__ == "__main__":
    main(sys.argv[1:])
