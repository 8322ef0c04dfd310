"""Standalone serving process for cross-process load legs.

Runs a full ``ClusterServing`` pipeline over a ``FileQueue`` spool so
real OS-process clients (``loadgen/client_main.py`` or the in-process
kill-leg client) can reach it from outside.  The model is built
DETERMINISTICALLY — seeded weights, seeded data, reset name scope — so
every process that runs this module produces the identical fingerprint
and a successor process warm-starts from the predecessor's persistent
compile cache with zero live compiles.

The process periodically dumps a status JSON (atomic replace) carrying
the warm-start proof (``compile_count``, ``warm_count``, cache event
counts) plus serving health; the kill leg reads it instead of scraping
logs.  SIGTERM stops cleanly (final status dump, exit 0); SIGKILL is
the point — the kill leg sends it mid-storm.

Pod mode (``--pod-processes N``, docs/SERVING.md "Pod-scale serving"):
the processes of one pod join a ``jax.distributed`` coordination
service.  Process 0 (the lead) runs the serving pipeline with a mesh
replica (``--mesh-replicas``) over a sharded-table model
(``--model bag``), every mesh dispatch gated by the pod's deadline
barrier; processes > 0 are member hosts that run the matching barrier
loop.  SIGKILLing a member mid-storm times the lead's next dispatch
barrier out within ``--barrier-timeout`` seconds, quarantining the
whole mesh replica atomically while the lead keeps serving on its
single-chip replica — the pod kill leg
(``loadgen/harness.py::run_pod_kill_leg``) drives exactly that.

Usage::

    python -m analytics_zoo_tpu.loadgen.server_main \
        --queue-root /tmp/spool --cache-dir /tmp/cache \
        --status-file /tmp/server.status.json
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import threading
import time
from typing import Any, Dict


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--queue-root", required=True)
    p.add_argument("--queue-name", default="loadgen_stream")
    p.add_argument("--cache-dir", required=True)
    p.add_argument("--status-file", required=True)
    p.add_argument("--slo-p99-ms", type=float, default=1000.0)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--decode-workers", type=int, default=2)
    p.add_argument("--max-batch-delay-ms", type=float, default=3.0)
    p.add_argument("--status-interval", type=int, default=2,
                   help="dump status every N supervisor ticks")
    p.add_argument("--autoscale", action="store_true")
    # pod mode (docs/SERVING.md "Pod-scale serving")
    p.add_argument("--model", default="dense", choices=["dense", "bag"],
                   help="'bag' = the sharded-embedding-table model the "
                        "mesh replica shards over the model axis")
    p.add_argument("--pod-processes", type=int, default=0,
                   help="> 1 joins a jax.distributed pod of this size")
    p.add_argument("--pod-id", type=int, default=0,
                   help="this process's id in the pod (0 = lead)")
    p.add_argument("--pod-port", type=int, default=0,
                   help="coordination-service port (lead hosts it)")
    p.add_argument("--pod-name", default="pod",
                   help="pod name (prefixes the dispatch barriers)")
    p.add_argument("--local-devices", type=int, default=0,
                   help="force N virtual CPU devices (mesh replicas "
                        "need >= 2)")
    p.add_argument("--barrier-timeout", type=float, default=2.0,
                   help="dist_barrier_timeout_s: a member missing a "
                        "dispatch barrier this long is presumed dead")
    p.add_argument("--follower-idle-timeout", type=float, default=600.0,
                   help="member hosts give up after this long with no "
                        "dispatch barrier from the lead (normally they "
                        "exit when the lead's coordination service "
                        "goes away — a member must NOT time a live "
                        "barrier out, or the lead's next arrival at it "
                        "fails spuriously)")
    p.add_argument("--mesh-replicas", type=int, default=0,
                   help="mesh-replica slots to plan (needs --model bag)")
    return p.parse_args(argv)


def build_model():
    """The deterministic two-layer Dense model shared by every loadgen
    server process (same idiom as tests/multiprocess_worker.py's
    ``serving_warm`` scenario: seeded context + seeded data => identical
    fingerprint in every process)."""
    import numpy as np

    from analytics_zoo_tpu.deploy import InferenceModel
    from analytics_zoo_tpu.nn import Sequential, reset_name_scope
    from analytics_zoo_tpu.nn.layers.core import Activation, Dense
    from analytics_zoo_tpu.train.optimizers import Adam

    buckets = (1, 4, 8)
    in_dim, out_dim = 12, 4
    rs = np.random.RandomState(0)
    reset_name_scope()
    net = Sequential([Dense(16, input_shape=(in_dim,)),
                      Activation("relu"), Dense(out_dim)])
    net.compile(optimizer=Adam(1e-2), loss="mse")
    x = rs.randn(32, in_dim).astype(np.float32)
    net.fit(x, rs.randn(32, out_dim).astype(np.float32), batch_size=16,
            nb_epoch=1, verbose=False)
    return InferenceModel.from_keras_net(net, net.estimator.params,
                                         net.estimator.state,
                                         batch_buckets=buckets)


def build_bag_model():
    """The deterministic sharded-table model for pod mode: a single
    int32-ids input through a ``ShardedEmbeddingTable`` mean-bag into a
    Dense head.  Weights are the SEEDED INITIALIZERS, not a fit — in
    pod mode this process has already joined a multi-process
    ``jax.distributed`` runtime, and a training fit there would issue
    global-mesh collectives the member hosts never join.  Seeded init
    is just as deterministic, so every pod generation produces the
    identical fingerprint and warm-starts its predecessor's compile
    cache — including the mesh-sharded forward flavor (cache keys fold
    the mesh).  Contract constants (ids dim 4, vocab 64) match
    ``harness.POD_IN_DIM`` / ``harness.POD_VOCAB``."""
    import jax.numpy as jnp

    from analytics_zoo_tpu.deploy import InferenceModel
    from analytics_zoo_tpu.nn import Input, Model, reset_name_scope
    from analytics_zoo_tpu.nn.layers.core import Dense
    from analytics_zoo_tpu.nn.layers.sharded_embedding import \
        ShardedEmbeddingTable

    reset_name_scope()
    ids = Input(shape=(4,), dtype=jnp.int32, name="ids")
    bag = ShardedEmbeddingTable(64, 8, combiner="mean",
                                name="embed")(ids)
    net = Model([ids], Dense(4, name="head")(bag), name="default")
    net._sharded_tables = ("embed",)
    net.compile(optimizer="adam", loss="mse")
    # NOT est._ensure_built: that device_puts the params onto the
    # CONTEXT mesh, which under a multihost pod spans every process —
    # a cross-process collective the member hosts never join.  A plain
    # local jit runs the same seeded initializers entirely in-process.
    import jax
    est = net.estimator
    params, state = jax.jit(
        lambda r: est.model.init(r, (2, 4)))(jax.random.PRNGKey(0))
    return InferenceModel.from_keras_net(net, params, state,
                                         batch_buckets=(1, 4, 8))


def follower_main(args) -> int:
    """A pod member host: arrive at every ``zoo_pod_dispatch_*``
    deadline barrier the lead's mesh dispatches enter.  Exits 0 when
    the barriers stop coming (lead finished or died — surfaced as a
    ``HostLostError`` timeout after ``--follower-idle-timeout``).  The
    pod kill leg SIGKILLs this process mid-storm; dying between
    barriers IS the scenario."""
    from analytics_zoo_tpu.core.context import dist_barrier
    from analytics_zoo_tpu.robust.errors import HostLostError

    stop_evt = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop_evt.set())
    signal.signal(signal.SIGINT, lambda *_: stop_evt.set())
    _dump_status(args.status_file,
                 {"ready": True, "pod_follower": True,
                  "pod_id": args.pod_id, "pid": os.getpid()})
    seq = 0
    while not stop_evt.is_set():
        seq += 1
        try:
            dist_barrier(f"zoo_pod_dispatch_{args.pod_name}_{seq}",
                         timeout_s=args.follower_idle_timeout,
                         phase="dispatch")
        except HostLostError:
            break
        except Exception:
            break       # coordination service gone (lead exited)
    _dump_status(args.status_file,
                 {"ready": True, "pod_follower": True,
                  "pod_id": args.pod_id, "pid": os.getpid(),
                  "barriers": seq - 1, "t": time.time()})
    # skip the distributed shutdown handshake: the lead (which hosts
    # the coordination service) may already be gone
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(0)


def _dump_status(path: str, payload: Dict[str, Any]) -> None:
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    os.replace(tmp, path)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.local_devices:
        # virtual devices exist on the CPU platform only: asking for
        # them IS asking for the CPU (two pod members on one host cannot
        # share a chip); the status file says so under "device"
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.local_devices}").strip()
    from analytics_zoo_tpu.core.context import (describe_devices,
                                                enable_compile_cache)
    enable_compile_cache()
    if args.pod_processes > 1:
        from analytics_zoo_tpu import init_zoo_context
        init_zoo_context(
            multihost=True,
            coordinator_address=f"127.0.0.1:{args.pod_port}",
            num_processes=args.pod_processes,
            process_id=args.pod_id,
            dist_barrier_timeout_s=args.barrier_timeout)
        if args.pod_id != 0:
            return follower_main(args)

    from analytics_zoo_tpu.deploy import ClusterServing, ServingConfig
    from analytics_zoo_tpu.deploy.serving import FileQueue, PodCoordinator

    model = build_bag_model() if args.model == "bag" else build_model()
    mesh = roster = pod = None
    if args.mesh_replicas > 0:
        import jax
        import numpy as np

        from analytics_zoo_tpu.core.context import HostRoster

        devs = jax.local_devices()
        ways = 2 if len(devs) >= 2 else 1
        # the mesh replica shards over the lead's LOCAL devices; the
        # pod barrier is what crosses the process boundary
        mesh = jax.sharding.Mesh(
            np.asarray(devs[:ways]).reshape(1, ways), ("data", "model"))
        roster = HostRoster(list(range(max(1, args.pod_processes))))
        if args.pod_processes > 1:
            pod = PodCoordinator(roster, args.pod_id,
                                 name=args.pod_name,
                                 barrier_timeout_s=args.barrier_timeout)
    q = FileQueue(args.queue_root, name=args.queue_name)
    cfg = ServingConfig(
        batch_size=args.batch_size, poll_timeout_s=0.05,
        max_batch_delay_ms=args.max_batch_delay_ms,
        decode_workers=args.decode_workers,
        supervisor_interval_s=0.1,
        compile_cache_dir=args.cache_dir,
        slo_p99_ms={"default": args.slo_p99_ms},
        mesh_replicas=args.mesh_replicas,
        autoscale=args.autoscale, autoscale_interval_s=0.2,
        autoscale_cooldown_s=0.5)
    srv = ClusterServing({"default": model}, q, cfg, mesh=mesh,
                         roster=roster, pod=pod).start()

    # Full bucket coverage through the REPLICA dispatch path before
    # declaring ready: replica programs carry their target device in
    # the cache signature, so predict()-side coverage would persist a
    # different flavor than the one the pipeline executes.  The cold
    # process stores every (bucket, device) executable; a successor
    # warm-starts the whole set and serves the storm with zero live
    # compiles.
    import numpy as np
    if args.model == "bag":
        xcov = np.random.RandomState(1).randint(
            0, 64, (8, 4)).astype(np.int32)
    else:
        xcov = np.random.RandomState(1).randn(8, 12).astype(np.float32)
    rep = model.replica_forwards(n=1)[0]
    for b in model.batch_buckets:
        rep.harvest(rep.dispatch([xcov[:b]]))
    if mesh is not None and args.mesh_replicas > 0:
        # cover the mesh-sharded flavor too (its cache signature folds
        # the shard mesh), bypassing the pod barrier: a successor pod
        # must warm-start the WHOLE executable set, not just the
        # single-chip one.  Storm-time mesh dispatches then never
        # compile live — the pod kill leg's warm_compile_count==0 pin.
        srep = model.shard_replica(mesh)
        for b in model.batch_buckets:
            srep.harvest(srep.dispatch([xcov[:b]]))

    device = describe_devices()

    def status_payload() -> Dict[str, Any]:
        h = srv.health()
        audit = srv.autoscale_audit()
        return {
            "ready": True,
            "pid": os.getpid(),
            "device": device,
            "t": time.time(),
            "fingerprint": model.fingerprint(),
            "compile_count": int(model.compile_count),
            "warm_count": int(model.warm_count),
            "cache": h.get("compile_cache"),
            "records_served": h.get("records_served"),
            "queue": h.get("queue"),
            "models": h.get("models"),
            "mesh": h.get("mesh"),
            "pod_id": args.pod_id if args.pod_processes > 1 else None,
            "autoscale_flaps": (audit or {}).get("flaps"),
        }

    def dump() -> None:
        try:
            _dump_status(args.status_file, status_payload())
        except Exception:           # status is best-effort telemetry
            pass

    dump()                          # the readiness barrier for callers
    srv.add_scenario_check("loadgen_status_dump", dump,
                           every=args.status_interval)

    stop_evt = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: stop_evt.set())
    signal.signal(signal.SIGINT, lambda *_: stop_evt.set())
    while not stop_evt.is_set():
        stop_evt.wait(0.2)
    srv.stop()
    dump()                          # final post-traffic truth
    if args.pod_processes > 1:
        # skip the distributed shutdown handshake: a pod member this
        # lead outlived (the kill leg's SIGKILLed follower) can never
        # arrive at it
        sys.stdout.flush()
        sys.stderr.flush()
        os._exit(0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
