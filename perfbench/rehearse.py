"""Compile a cell's programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python perfbench/rehearse.py --workload <cell>

Builds the cell's fleet small on the CPU (so no full-size array is
made), then lowers the chunk program the window drives, and the chunk
sampler that feeds it, from shapes at the cell's full size, and compiles
both with the TPU compiler for one chip of a v5e:2x2 (or all four, for a
sharded cell). Prints ``memory_analysis()`` per program and device, and
whether the Pallas kernel (``tpu_custom_call``) and collectives are in
the compiled chunk. Nothing runs, so it gives bytes and HLO, never a
time. The rehearsal steers the program's backend checks to the chip's
branch in this process only; the benchmark never does.
"""
from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from perfbench import harness
    from repro.compat import make_mesh
    from repro.config import ProtocolConfig, TrainConfig
    from repro.core import shard
    from repro.core.protocol import DecentralizedLearner
    from repro.data.pipeline import LearnerStreams
    from repro.kernels import ops

    cell = harness.Cell(args.workload)
    tr, cfg_json = cell.traffic, cell.config
    init_fn, loss_fn = cell.member.program(cfg_json)
    M, chunk, batch = int(tr["m"]), int(tr["chunk"]), int(tr["batch"])
    sharded = tr["layout"] == "sharded"
    small = 8 * max(1, int(tr["shard_devices"]))
    proto = ProtocolConfig(kind=tr["protocol"], b=int(tr["b"]),
                           delta=float(tr["delta"]),
                           augmentation=tr["augmentation"],
                           layout="flat" if sharded else tr["layout"])
    dl = DecentralizedLearner(
        loss_fn, init_fn, small, proto,
        TrainConfig(optimizer=tr["optimizer"],
                    learning_rate=float(tr["learning_rate"])))
    data = importlib.import_module(
        f"perfbench.data.{cfg_json['data']['generator']}")
    streams = LearnerStreams(data.make(0, cfg_json["data"]["args"]), M,
                             batch=batch)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the program picks its kernel path from the backend; this process's
    # backend is the CPU, so steer it to the chip's branch
    ops._interp = lambda: False
    jax.default_backend = lambda: "tpu"

    if sharded:
        n = int(tr["shard_devices"])
        mesh = make_mesh((n,), (shard.FLEET_AXIS,), devices=topo.devices[:n])
        fleet = shard.FleetSharding(mesh=mesh, m=M)
        dl.fleet = fleet
        dl.spec = dl.spec.with_params(layout="sharded", shard_devices=n)

        def sh(shape, axis=0):
            lead = axis is not None and len(shape) > axis and shape[axis] == M
            return (fleet.row_sharding(len(shape), axis) if lead
                    else fleet.replicated())
    else:
        one = SingleDeviceSharding(topo.devices[0])

        def sh(shape, axis=0):
            return one

    def grow(x, axis=0):
        shape = list(jnp.shape(x))
        if len(shape) > axis and shape[axis] == small:
            shape[axis] = M
        shape = tuple(shape)
        return jax.ShapeDtypeStruct(shape, jnp.result_type(x),
                                    sharding=sh(shape, axis))

    def keep(x):
        shape = tuple(jnp.shape(x))
        return jax.ShapeDtypeStruct(shape, jnp.result_type(x),
                                    sharding=sh(shape, None))

    sampler = streams._chunk_sampler(chunk)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=sh((2,), None))
    concept = jax.tree.map(keep, streams.source.concept())
    comp_s = sampler.lower(key, concept).compile()
    batches = jax.eval_shape(sampler, jax.random.PRNGKey(0),
                             streams.source.concept())[1]
    batches = jax.tree.map(lambda x: grow(x, 1), batches)

    st = dl.sync_state
    state = st._replace(ref=jax.tree.map(keep, st.ref), v=keep(st.v),
                        rng=keep(st.rng), step=keep(st.step),
                        extra=jax.tree.map(grow, st.extra))
    params = jax.tree.map(grow, dl.params)
    opt = jax.tree.map(grow, dl.opt_state)
    chunk_fn = jax.jit(dl._make_chunk())
    if sharded:
        with shard.use_fleet(fleet):
            comp_c = chunk_fn.lower(params, opt, state, batches).compile()
    else:
        comp_c = chunk_fn.lower(params, opt, state, batches).compile()

    for name, comp in (("sampler", comp_s), ("chunk", comp_c)):
        ma = comp.memory_analysis()
        print(f"[{cell.name}] {name}: arguments "
              f"{ma.argument_size_in_bytes} outputs "
              f"{ma.output_size_in_bytes} temp {ma.temp_size_in_bytes} "
              f"alias {ma.alias_size_in_bytes} bytes per device")
    text = comp_c.as_text()
    found = {k: (k in text) for k in (
        "tpu_custom_call", "all-gather", "all-reduce", "all-to-all",
        "collective-permute", "reduce-scatter")}
    print(f"[{cell.name}] chunk HLO: {found}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
