"""Compile a cell's step at full size for a chip that is not attached.

    JAX_PLATFORMS=cpu python -m chipbench.aot [--workload <cell>] ...

The TPU compiler is installed in the CPU sandbox and compiles for a described
``v5e:2x2`` host (on-chip-measurement guide, section 2). This prints the
compiler's account of each cell's step: bytes per device, Mosaic calls and
collectives in the program. It refuses what the chip's compiler would refuse
(a step that does not fit, a kernel that cannot be partitioned) at no chip
time. Nothing runs, so nothing here is a time; ``PERF.md`` quotes these
numbers as "(compiler, PR n)".
"""

import argparse
import json
import os
import re
import time


def step_bytes(memory_analysis):
    """Bytes one device needs to run a compiled step: arguments, temporaries
    and outputs, less the outputs that reuse a donated argument."""
    ma = memory_analysis
    return int(ma.argument_size_in_bytes + ma.temp_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def compile_cell(catalog, workload, topology):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    cell, config, traffic = catalog.cell(workload)
    runner = catalog.module("runners", config["runner"])
    job = runner.build(config, traffic, topology.devices[:cell["chips"]])
    replicated = NamedSharding(job.mesh, P())

    def shardings_of(fn, *abstract):
        """The shardings the program's own code gives fn's outputs."""
        return jax.jit(fn).lower(*abstract).compile().output_shardings

    def with_shardings(shapes, shardings):
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            shapes, shardings)

    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    host_batch = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=replicated),
        job.draw_batch(np.random.RandomState(0), job.batch))
    params, opt_state, batch = job.abstract_args()
    params, opt_state = with_shardings(
        (params, opt_state), shardings_of(job.init_fn, key))
    batch = with_shardings(batch, shardings_of(job.place, host_batch))
    t0 = time.perf_counter()
    compiled = job.jitted.lower(params, opt_state, batch).compile()
    text = compiled.as_text()
    ma = compiled.memory_analysis()
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    return {
        "workload": workload, "chips": cell["chips"],
        "compile_s": round(time.perf_counter() - t0, 1),
        "parameters": n_params,
        "parameter_leaves": len(jax.tree.leaves(params)),
        "argument_gib": ma.argument_size_in_bytes / 2**30,
        "temp_gib": ma.temp_size_in_bytes / 2**30,
        "step_gib": step_bytes(ma) / 2**30,
        "mosaic_calls": text.count("tpu_custom_call"),
        "collectives": {
            k: len(re.findall(rf"[ )]{k}(?:-start)?\(", text))
            for k in ("all-reduce", "all-gather", "reduce-scatter",
                      "collective-permute", "all-to-all")},
    }


def main(argv=None):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", action="append",
                    help="a cell of BENCHMARK.json; default: every cell")
    args = ap.parse_args(argv)
    from jax.experimental import topologies
    from paddle_tpu.ops.pallas import registry

    from chipbench.catalog import Catalog
    catalog = Catalog()
    topology = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    # what the registry's probe answers on the chip: here jax.devices() is
    # the CPU, and `auto` would hand every kernel its reference body
    registry.platform = lambda: "tpu"
    for name in args.workload or [w["name"]
                                  for w in catalog.spec["workloads"]]:
        print(json.dumps(compile_cell(catalog, name, topology)), flush=True)


if __name__ == "__main__":
    main()
