"""Long-context attention with sequence parallelism (ring attention).

The reference's TransformerLayer/BERT materialize the full O(L²)
attention matrix on one host, bounding sequence length by single-node
memory (SURVEY.md §5.7).  Here the sequence axis is sharded over the
mesh: each device holds L/n of Q/K/V, K/V shards rotate around the ring
via ICI neighbour exchanges, and no device ever materializes more than
an (L/n x L/n) tile — context length scales linearly with devices.

    python ring_attention_example.py                # L=4096 over 8 CPU devs
    python ring_attention_example.py --length 8192
    python ring_attention_example.py --real         # real multi-chip slice
"""

import argparse
import os


def _ensure_devices(n: int) -> None:
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
    os.environ["JAX_PLATFORMS"] = "cpu"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--length", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--dim", type=int, default=32)
    ap.add_argument("--real", action="store_true")
    args = ap.parse_args()
    if not args.real:
        _ensure_devices(args.devices)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from analytics_zoo_tpu.ops.attention import reference_attention
    from analytics_zoo_tpu.parallel import ring_self_attention

    n = args.devices
    if len(jax.devices()) < n:
        raise SystemExit(f"need {n} devices, have {len(jax.devices())}")
    L = args.length - args.length % n        # shard evenly
    rs = np.random.RandomState(0)
    shape = (1, args.heads, L, args.dim)
    q = jnp.asarray(rs.randn(*shape).astype(np.float32))
    k = jnp.asarray(rs.randn(*shape).astype(np.float32))
    v = jnp.asarray(rs.randn(*shape).astype(np.float32))

    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(n), ("sp",))
    out = ring_self_attention(q, k, v, mesh, "sp", causal=True)
    print(f"ring attention: L={L} over {n} devices "
          f"(per-device sequence {L // n}), out {out.shape}")

    # cross-check against full attention (only feasible at modest L)
    if L <= 4096:
        ref = reference_attention(q, k, v, causal=True)
        err = float(jnp.max(jnp.abs(out - ref)))
        print(f"max |ring - full| = {err:.2e}")
        assert err < 2e-4
    # gradients flow through the ring (ppermute has a transpose rule)
    g = jax.grad(lambda qq: jnp.sum(
        ring_self_attention(qq, k, v, mesh, "sp", causal=True) ** 2))(q)
    print(f"grad through ring ok: |dq| = {float(jnp.abs(g).mean()):.4f}")
    print("done: long-context attention sharded over the sequence axis")


if __name__ == "__main__":
    main()
