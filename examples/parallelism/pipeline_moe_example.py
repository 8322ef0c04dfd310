"""Pipeline + expert parallelism on a virtual device mesh.

Both regimes are TPU-native capabilities beyond the reference (SURVEY.md
§2.4 lists PP and EP as explicit gaps in Analytics Zoo).  Run anywhere:

    python pipeline_moe_example.py                 # 8 virtual CPU devices
    python pipeline_moe_example.py --devices 4
    python pipeline_moe_example.py --real          # real multi-chip slice

With ``--real`` no virtual topology is forced and the same code shards
over ICI.
"""

import argparse
import os


def _ensure_devices(n: int) -> None:
    """Fake an n-device CPU topology before jax is imported (same trick
    as tests/conftest.py)."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--real", action="store_true",
                    help="use the real device topology (no CPU fakes)")
    args = ap.parse_args()
    if not args.real:
        _ensure_devices(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from analytics_zoo_tpu import init_zoo_context
    from analytics_zoo_tpu.core.context import get_zoo_context
    from analytics_zoo_tpu.nn.layers import SparseMoE
    from analytics_zoo_tpu.parallel import (ExpertParallel, PipelineParallel,
                                            stack_stage_params)

    if len(jax.devices()) < args.devices:
        raise SystemExit(f"need {args.devices} devices, have "
                         f"{len(jax.devices())}; run with JAX_PLATFORMS=cpu")

    # ---- pipeline parallelism: an MLP stack, one stage per device ------
    S, D, B = args.devices, 64, 16 * args.devices
    rs = np.random.RandomState(0)

    def stage_fn(p, x):
        return jnp.tanh(x @ p["w"] + p["b"])

    stages = [{"w": jnp.asarray(rs.randn(D, D).astype(np.float32) * 0.2),
               "b": jnp.zeros((D,), jnp.float32)} for _ in range(S)]
    stacked = stack_stage_params(stages)
    mesh = Mesh(np.asarray(jax.devices()[:S]).reshape(S), ("pipe",))
    pp = PipelineParallel(mesh, n_microbatches=args.microbatches)
    stacked = pp.shard_params(stacked)      # each stage lives on its device
    x = jnp.asarray(rs.randn(B, D).astype(np.float32))
    y = jnp.asarray(rs.randn(B, D).astype(np.float32))

    @jax.jit
    def pp_step(sp):
        loss, g = jax.value_and_grad(
            lambda sp: jnp.mean((pp.apply(stage_fn, sp, x) - y) ** 2))(sp)
        return jax.tree_util.tree_map(lambda p, gg: p - 0.05 * gg, sp, g), loss

    for i in range(args.steps):
        stacked, loss = pp_step(stacked)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"[pipeline {S} stages] step {i:3d} loss {float(loss):.5f}")

    # ---- expert parallelism: sparse MoE sharded over an expert axis ----
    init_zoo_context(mesh_shape=(args.devices // 2, 2),
                     axis_names=("data", "expert"))
    ctx = get_zoo_context()
    moe = SparseMoE(n_experts=4, hidden_dim=128, top_k=2,
                    capacity_factor=2.0, expert_axis="expert")
    params, state = moe.init(jax.random.PRNGKey(0), (B, D))
    params = jax.device_put(
        params, ExpertParallel(axis="expert").param_shardings(ctx.mesh,
                                                              params))

    @jax.jit
    def ep_step(p):
        def loss_fn(p):
            out, ns = moe.call(p, state, x)
            return jnp.mean((out - y) ** 2) + 0.01 * ns["aux_loss"]
        loss, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda pp_, gg: pp_ - 0.05 * gg,
                                      p, g), loss

    for i in range(args.steps):
        params, loss = ep_step(params)
        if i % 5 == 0 or i == args.steps - 1:
            print(f"[moe 4 experts over 'expert' axis] step {i:3d} "
                  f"loss {float(loss):.5f}")
    print("done: pipeline + expert parallel both trained")


if __name__ == "__main__":
    main()
