"""End-to-end training driver: ``--arch <id>`` selects any assigned config.

By default it builds the arch's reduced (smoke) config, which runs on a CPU
in seconds; ``--full`` builds the published config instead (``dcn-v2``'s
fits one 16 GB chip; ``dlrm-mlperf``'s does not). Either config runs on
whatever backend JAX finds, through the same path: the FeatureBox FE
pipeline (recsys archs), the jitted train step, async checkpointing,
restart. ``main(argv)`` returns the run's summary (losses, steps, runner
stats), and a non-finite loss ends the run with an error.

Two batch sources:

* default — in-memory ``synthetic_batch`` per step (no disk in the loop);
* ``--data-dir DIR`` (recsys only) — stream ``.fbshard`` raw-log shards
  through a compiled FeatureBox ``FeaturePlan`` with
  ``repro.io.StreamingLoader``: reader threads pull shards off disk
  (decoding only the plan's ``required_columns``), the FE worker extracts
  features for batch i+1 while the device trains on batch i. Pick the
  feature scenario with ``--spec ads_ctr|dlrm|bst``; regenerate shards
  with ``repro.fe.datagen.write_log_shards`` (see ``--gen-shards``).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-mlperf --steps 50
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --steps 10
  PYTHONPATH=src python -m repro.launch.train --arch dlrm-mlperf \
      --data-dir /tmp/adslog --gen-shards 8 --steps 16 --spec dlrm
"""

from __future__ import annotations

import argparse
import itertools
import math
import time
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_arch, list_archs
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import LoopConfig, run_training
from repro.train.optimizer import adamw


def synthetic_batch(family: str, cfg, batch: int, step: int) -> Dict[str, Any]:
    rng = np.random.default_rng(step)
    if family == "lm":
        toks = rng.integers(0, cfg.vocab, (batch, 64)).astype(np.int32)
        return {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
    if family == "recsys":
        b = {
            "sparse": jnp.asarray(np.stack(
                [rng.integers(0, v, batch) for v in cfg.vocab_sizes[:cfg.n_sparse]],
                axis=1).astype(np.int32)),
            "label": jnp.asarray((rng.random(batch) < 0.25).astype(np.float32)),
        }
        if cfg.n_dense:
            b["dense"] = jnp.asarray(
                rng.exponential(1.0, (batch, cfg.n_dense)).astype(np.float32))
        if cfg.kind == "bst":
            b["seq"] = jnp.asarray(
                rng.integers(0, cfg.vocab_sizes[0], (batch, cfg.seq_len)).astype(np.int32))
        return b
    # gnn
    from repro.models.gnn import random_graph
    g = random_graph(200, 800, cfg.d_in, cfg.n_classes, seed=step)
    return {k: jnp.asarray(v) for k, v in g.items()}


# Reference oracle for the compiled boundary (kept importable under the old
# name; repro.fe.modelfeed.compile is the production path).
from repro.fe.modelfeed import fe_env_to_model_batch_ref as fe_env_to_model_batch  # noqa: E402,E501


def run_streaming(args, spec, cfg, key, opt, check_report=None) -> Dict[str, Any]:
    """Stream raw-log shards from disk through FE into the train step.

    The stage->train boundary is compiled: ``repro.fe.modelfeed`` derives
    the spec->arch adaptation from the plan's ``OutputLayout`` at compile
    time and traces it INSIDE the train step's jit (``--adapt fused``,
    default) — one fused dispatch per step, versus ~10 eager per-step jnp
    ops for the legacy adapter (``--adapt eager``, kept as the measurable
    baseline). The sparse working-set capacity is tuned from the dataset
    manifest's rows hint so the dedup'd embedding path runs by default,
    ``--device-feed arena`` stages per-field id vectors straight into the
    ring arena (``split_sparse_fields``), and the staged batch + params +
    optimizer state are donated through the jit (``--no-donate`` opts out)
    with the feeder's ``donation_fence`` accounting the reuse. The train
    state is created by the chosen step's init, in one jit and in its final
    placement (row-sharded on a mesh). Returns the run summary.
    """
    import dataclasses
    import os

    from repro.core import DeviceFeeder, PipelinedRunner
    from repro.embedding.psfeed import WS_META, WS_SLOTS, HierarchyFeed
    from repro.fe import featureplan, get_spec
    from repro.io.dataset import ShardDataset
    from repro.io.stream import StreamingLoader
    from repro.models import recsys as R

    if spec.family != "recsys":
        raise SystemExit(
            f"--data-dir streaming runs the FeatureBox FE pipeline and is "
            f"only wired for recsys archs (got family={spec.family!r})")

    if args.gen_shards:
        from repro.fe.datagen import write_log_shards
        paths = write_log_shards(args.data_dir, n_shards=args.gen_shards,
                                 rows_per_shard=args.batch, seed=0)
        print(f"wrote {len(paths)} shards to {args.data_dir}")

    ds = ShardDataset(args.data_dir, host_id=args.host_id,
                      n_hosts=args.n_hosts)
    if not len(ds):
        raise SystemExit(
            f"host {args.host_id}/{args.n_hosts} got no shards: the dataset "
            f"has only {len(ds.shards)} shard(s); generate more or use "
            f"fewer hosts")
    plan = featureplan.compile(get_spec(args.spec))
    print(plan.summary())
    epochs = -(-args.steps // len(ds))  # enough passes for --steps
    chaos = None
    if args.chaos:
        if not args.fault_tolerant:
            raise SystemExit(
                "--chaos injects faults into the lease-based reader pool "
                "and needs the ordered fault-tolerant yield contract: "
                "pass --fault-tolerant")
        from repro.io.chaos import ChaosInjector
        chaos = ChaosInjector.from_spec(args.chaos)
        print(f"chaos: {len(chaos.events)} scheduled fault(s) "
              f"({args.chaos})")
    # Projection pushdown: only the columns the spec touches are decoded.
    # Shards are leased from a ShardServer (reap/retry/backup recovery);
    # --fault-tolerant additionally re-sequences completions into plan
    # order so a run with failures yields bit-identical data to one
    # without.
    loader = StreamingLoader(ds, workers=args.stream_workers,
                             prefetch=args.stream_prefetch, epochs=epochs,
                             shuffle=True, seed=0,
                             columns=plan.required_columns,
                             lease_timeout=args.lease_timeout,
                             chaos=chaos, ordered=args.fault_tolerant)
    ckpt = (CheckpointManager(args.checkpoint_dir)
            if args.checkpoint_dir else None)

    # Compile the stage->train boundary: static field remap + vocab modulo
    # + block synthesis, working-set capacity sized from the manifest.
    # Without a manifest rows hint the capacity is left untuned (0): the
    # train step then falls back to its always-safe batch-sized bound —
    # streaming batches are SHARD-sized, so sizing from --batch could
    # silently undersize the working set and drop ids.
    split = args.device_feed == "arena"
    if cfg.dedup_capacity:
        cfg = dataclasses.replace(cfg, dedup_capacity=0)  # re-tune per data
    mf = plan.model_feed(cfg, split_sparse_fields=split,
                         rows_hint=loader.rows_hint)
    cfg = mf.config
    mesh = None
    n_pods = n_data = 1
    if args.mesh:
        from repro.launch.mesh import make_train_mesh, parse_mesh_spec
        if args.mesh == "auto":
            # Elastic topology: size the mesh to whatever devices are
            # healthy right now. With --resume this is the remesh path —
            # checkpoint under one device count, restart under another,
            # and the restored state is re-placed on the new mesh.
            from repro.train.fault import elastic_remesh
            n_healthy = len(jax.devices())
            shape, _axes, n_used = elastic_remesh(
                n_healthy, model_parallel=1, pod_size=args.pod_size)
            n_pods, n_data = ((shape[0], shape[1]) if len(shape) == 3
                              else (1, shape[0]))
            print(f"elastic mesh: {n_healthy} healthy device(s) -> "
                  f"{n_pods}x{n_data} ({n_used} used)")
        else:
            n_pods, n_data = parse_mesh_spec(args.mesh)
        n_mesh_dev = n_pods * n_data
        if n_mesh_dev > 1 and args.device_feed != "off":
            raise SystemExit(
                "--mesh with more than one device requires --device-feed "
                "off: the staging arena is single-device; the mesh jit "
                "splits the host batch across the row shards itself")
        mesh = make_train_mesh(n_pods, n_data)
    comm = None
    if args.embedding == "hierarchy":
        # Embedding rows come from the hierarchical PS (SSD <- host cache
        # <- per-batch working set), pulled a batch ahead on a dedicated
        # pipeline stage; the train step consumes them via WS_SLOTS.
        if not cfg.dedup_capacity:
            raise SystemExit(
                "--embedding hierarchy needs a tuned working-set capacity "
                "and the dataset manifest has no rows hint — regenerate the "
                "shards (repro.fe.datagen writes the manifest)")
        raw_step, init_opt, _ = R.make_hierarchy_train_step(cfg, opt)
        # Embedding rows live in the PS file, not in params: dense tree
        # only (same fold_in enumeration, so dense init is bitwise
        # identical to the in-memory backend).
        params, opt_state = R.init_train_state(cfg, key, init_opt,
                                               include_embed=False)
        extra_slots = WS_SLOTS
    elif mesh is not None:
        # Data-parallel scale-out: table rows + Adagrad accumulators
        # sharded over the ('pod', 'data') mesh, two-stage dedup, and
        # hierarchical (compressed across pods) gradient reduction. On a
        # 1x1 mesh with --compress off this path is bitwise-identical to
        # the single-device step (tests/test_mesh.py).
        from repro.fe.modelfeed import dedup_capacity_hint
        from repro.train.compression import CommPlan, CommStats
        local_cap = 0
        if n_mesh_dev > 1 and loader.rows_hint:
            # stage-1 capacity: sized like the global working set, but for
            # one device's share of the batch rows
            local_cap = dedup_capacity_hint(
                cfg, max(1, loader.rows_hint // n_mesh_dev))
        raw_step, mesh_init, _ = R.make_mesh_train_step(
            cfg, opt, mesh=mesh, compress=args.compress,
            local_dedup_capacity=local_cap)
        # Born row-sharded: no device holds the whole table at any point.
        params, opt_state = R.init_train_state(cfg, key, mesh_init,
                                               mesh=mesh)
        rows_dev = max(1, (loader.rows_hint or args.batch) // n_mesh_dev)
        ids_dev = R.batch_id_count(cfg, rows_dev)
        comm = CommStats(plan=CommPlan.for_step(
            n_pods=n_pods, inner=n_data, compress=args.compress,
            hierarchical=True,
            capacity=cfg.dedup_capacity or ids_dev * n_mesh_dev,
            embed_dim=cfg.embed_dim,
            n_dense_elems=R.dense_param_elems(cfg),
            local_capacity=local_cap or ids_dev,
            ids_per_device=ids_dev))
        print(f"comm plan: {comm.summary()}")
        extra_slots = ()
    else:
        raw_step, init_opt, _ = R.make_sparse_train_step(cfg, opt)
        params, opt_state = R.init_train_state(cfg, key, init_opt)
        extra_slots = ()
    state = {"params": params, "opt": opt_state}
    del params, opt_state  # donated by the first step

    # Restart-from-latest, possibly across a remesh: the checkpoint holds
    # host arrays (topology-free), so restoring into the *current* state
    # structure and re-placing with shard_train_state adapts it to
    # whatever mesh this run resolved (the elastic_remesh contract).
    start_step = 0
    if args.resume:
        if ckpt is None:
            raise SystemExit("--resume requires --checkpoint-dir")
        restored = ckpt.restore_latest(state)
        if restored is None:
            print("resume: no checkpoint found; starting fresh")
        else:
            step0, state = restored
            prev_mesh = ckpt.latest_meta().get("mesh")
            if mesh is not None:
                state["params"], state["opt"] = R.shard_train_state(
                    mesh, state["params"], state["opt"])
            start_step = step0 + 1
            print(f"resume: restored step {step0} "
                  f"(saved mesh {prev_mesh}, current [{n_pods}, {n_data}])")

    layers = plan.layers
    feeder = None
    if args.device_feed == "arena":
        # Zero-copy feed: FE assembles batch_* outputs straight into
        # claimed arena views (no env->arena memcpy; FeedStats counts the
        # elided copies) — per-field id vectors, so the sparse feed lands
        # in the shape the dedup'd embedding lookup consumes. Arena sized
        # up front from the dataset manifest.
        ab = plan.arena_binding(split_sparse_fields=True)
        layers, feeder = ab.layers, ab.make_feeder(rows_hint=loader.rows_hint)
    elif args.device_feed == "on":
        # Third pipeline stage: batch i+1 is staged through the buffer-ring
        # device arena while batch i trains. Arena sized up front from the
        # dataset manifest via the loader's rows hint.
        feeder = DeviceFeeder(plan.feed_layout(), rows_hint=loader.rows_hint)

    fused = mf.make_step(
        raw_step, fused=(args.adapt == "fused"), donate=not args.no_donate,
        fence_cb=(feeder.donation_fence if feeder is not None else None),
        extra_slots=extra_slots)

    hier = None
    if args.embedding == "hierarchy":
        from repro.embedding.hierarchy import HierarchicalPS
        mt = cfg.multi_table()
        total_rows = int(mt.total_rows)
        dim = cfg.embed_dim + 1  # Adagrad accumulator colocated (last col)
        ps_dir = args.ps_dir or os.path.join(args.data_dir, "_ps")
        ps_path = os.path.join(ps_dir, f"{args.arch}.{args.spec}.ps.f32")
        scale = 1.0 / float(np.sqrt(cfg.embed_dim))

        def ps_init(s, e, rng):
            block = np.empty((e - s, dim), np.float32)
            block[:, :-1] = rng.uniform(-scale, scale, (e - s, cfg.embed_dim))
            block[:, -1] = 0.1  # make_sparse_train_step's embed_accum init
            return block

        ps = HierarchicalPS(ps_path, total_rows=total_rows, dim=dim,
                            host_cache_rows=args.host_cache_rows,
                            init_fn=ps_init)
        hier = HierarchyFeed(ps, mf)
        table_mb = total_rows * dim * 4 / 2**20
        line = (f"ps: table {table_mb:.1f} MiB ({total_rows} rows x {dim} "
                f"f32), host cache {args.host_cache_rows} rows, "
                f"SSD tier {ps_path}")
        if args.device_budget_mb:
            rel = ("EXCEEDS" if table_mb > args.device_budget_mb
                   else "fits in")
            line += (f" — {rel} the simulated device budget "
                     f"{args.device_budget_mb:.1f} MiB")
        print(line)

    losses = []
    step_seconds = []  # host clock per step, first one includes compile
    cost_args = []  # (params, opt, feed) ShapeDtypeStructs for --metrics
    from repro.obs.trace import get_tracer
    tracer = get_tracer()

    def step_fn(state, env):
        if args.metrics and not cost_args:
            # Shapes only (no data, no transfers): enough to lower the
            # boundary jit for HLO cost analysis after the run.
            from repro.launch.hlo_stats import abstractify
            feed = abstractify(fused.select_feed(env))
            if args.adapt == "eager":
                extras = {k: feed.pop(k) for k in extra_slots}
                feed = dict(jax.eval_shape(mf.apply, feed))
                feed.update(extras)
            p, o = abstractify((state["params"], state["opt"]))
            cost_args.append((p, o, feed))
        t0 = time.perf_counter()
        p, o, m = fused(state["params"], state["opt"], env)
        if hier is not None:
            # Async write-back: hand the updated working set to the PS
            # writer thread; the pull for batch i+2 waits on it, not us.
            hier.complete(env[WS_META], m.pop("ws_rows"), m.pop("ws_accum"))
        with tracer.span("train.sync"):
            losses.append(float(m["loss"]))  # blocks until the step lands
        step_seconds.append(time.perf_counter() - t0)
        if not math.isfinite(losses[-1]):
            raise FloatingPointError(
                f"non-finite loss {losses[-1]} at step {len(losses) - 1}")
        if comm is not None:
            comm.on_step()
        state = {"params": p, "opt": o}
        if ckpt is not None and len(losses) % args.checkpoint_every == 0:
            ckpt.save_async(start_step + len(losses) - 1, state,
                            meta={"mesh": [n_pods, n_data]})
        return state

    step_fn.feed_stats = mf.stats  # runners adopt the train-feed tier
    step_fn.comm_stats = comm      # runners adopt the comm tier (mesh only)

    runner = PipelinedRunner(layers, step_fn,
                             prefetch=args.stream_prefetch,
                             device_feed=feeder, ps_feed=hier)
    shard_iter = iter(loader)  # kept so the generator can be closed below
    t0 = time.perf_counter()
    try:
        state = runner.run(state, itertools.islice(shard_iter, args.steps))
    finally:
        # Close the generator explicitly (its finally finalizes the
        # loader's wall-clock stats) before stopping the reader pool —
        # islice abandonment alone leaves that to garbage collection.
        try:
            shard_iter.close()
        except ValueError:  # FE worker still holds it (join timed out)
            pass
        loader.close()
        if hier is not None:
            # Drain/flush handshake: every enqueued write-back lands on the
            # SSD tier before we read stats or exit (idempotent, no-raise).
            hier.drain()
        if ckpt is not None:
            ckpt.wait()
    # islice hides the loader from the runner's duck-typed stats capture
    runner.stats.ingest = loader.stats
    runner.stats.fault = loader.fault_stats
    dt = time.perf_counter() - t0
    s = runner.stats
    if not losses:
        raise SystemExit("streaming run consumed no batches")
    print(f"arch={args.arch} spec={args.spec} mode=streaming steps={s.batches} "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f} "
          f"({dt:.1f}s, {dt/max(s.batches,1)*1e3:.1f} ms/step; "
          f"fe={s.fe_seconds:.2f}s train={s.train_net_seconds:.2f}s "
          f"adapt={s.adapt_seconds:.3f}s wall={s.wall_seconds:.2f}s)")
    print(f"ingest: {loader.stats.summary()}")
    fs = loader.fault_stats
    if args.fault_tolerant or fs.reissued or fs.retries or fs.failed_workers:
        print(f"fault: {fs.summary()}")
    if chaos is not None:
        fired = {k: v for k, v in chaos.fired.items() if v}
        print(f"chaos: fired {fired or 'nothing'}"
              f"{'' if chaos.exhausted() else ' (schedule NOT exhausted)'}")
    if s.feed is not None:
        print(f"device-feed: {s.feed.summary()}")
    if s.train_feed is not None:
        print(f"train-feed: {s.train_feed.summary()} "
              f"(capacity={cfg.dedup_capacity})")
    if hier is not None:
        print(f"ps: {hier.summary()} ps_stage={s.ps_seconds:.2f}s")
    if comm is not None:
        print(f"comm: {comm.summary()}")
    if args.metrics:
        from repro.launch.hlo_stats import step_cost
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry.from_pipeline(s)
        if check_report is not None:
            reg.register("check", check_report)
        if cost_args:
            tot = step_cost(fused.jitted, *cost_args[0])
            reg.register("hlo", tot)
            _print_hlo_cost(tot)
        print("metrics:")
        print(reg.to_json())
    return {"arch": args.arch, "config": cfg, "steps": s.batches,
            "losses": losses, "step_seconds": step_seconds, "stats": s,
            "table_shards": _table_shards(state["params"])}


def _table_shards(params) -> Any:
    """(platform, device id, rows) of each addressable shard of the
    embedding table, or None where the table lives off-device (PS)."""
    embed = params.get("embed")
    if embed is None:
        return None
    return [(sh.device.platform, sh.device.id, int(sh.data.shape[0]))
            for sh in embed.addressable_shards]


def _print_hlo_cost(tot) -> None:
    """Roofline-style per-step summary from loop-aware HLO analysis."""
    print(f"hlo/step: {tot.flops/1e9:.3f} GFLOP "
          f"hbm={tot.bytes/2**20:.1f}MiB "
          f"(tpu-corrected {tot.bytes_tpu_corrected/2**20:.1f}MiB) "
          f"collective={tot.collective_total/2**20:.1f}MiB "
          f"intensity={tot.flops/max(tot.bytes, 1.0):.2f} flop/byte")


def main(argv=None) -> Dict[str, Any]:
    """Parse ``argv`` (default ``sys.argv[1:]``), train, and return the run
    summary: ``losses``, ``steps``, runner ``stats`` and the ``config``."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list_archs())
    ap.add_argument("--full", action="store_true",
                    help="build the arch's published config instead of its "
                         "reduced smoke config")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest checkpoint from "
                         "--checkpoint-dir before training (streaming "
                         "mode); with --mesh auto the restored host arrays "
                         "are re-placed on the mesh the current device "
                         "count resolves to — the elastic remesh-resume "
                         "path")
    # fault tolerance (repro.train.fault + repro.io.chaos)
    ap.add_argument("--fault-tolerant", action="store_true",
                    help="ordered fault-tolerant streaming: yield shards "
                         "in plan order through a reorder buffer so a run "
                         "with worker failures is bit-identical to one "
                         "without, and print the fault.* recovery summary "
                         "(lease scheduling itself is always on)")
    ap.add_argument("--lease-timeout", type=float, default=30.0,
                    help="seconds without a heartbeat before the reaper "
                         "returns a shard reader's lease to the queue")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="inject scheduled faults into the reader pool "
                         "(requires --fault-tolerant): comma-separated "
                         "kind@shard[:point][:arg] events, e.g. "
                         "'kill@3,transient@1:read:2,delay@2:read:0.05,"
                         "kill@5:commit' — see repro.io.chaos")
    ap.add_argument("--pod-size", type=int, default=None,
                    help="devices per pod for --mesh auto: lets "
                         "elastic_remesh pick a 3-axis (pod, data, model) "
                         "topology when enough devices are healthy")
    # streaming-ingest mode (repro.io)
    ap.add_argument("--data-dir", default=None,
                    help="stream .fbshard raw-log shards instead of "
                         "in-memory synthetic batches (recsys only)")
    from repro.fe.specs import list_specs
    ap.add_argument("--spec", default="ads_ctr", choices=list_specs(),
                    help="feature spec compiled for --data-dir streaming "
                         "(declarative FE scenario preset)")
    ap.add_argument("--gen-shards", type=int, default=0,
                    help="generate this many shards into --data-dir first")
    ap.add_argument("--device-feed", default="off",
                    choices=["on", "off", "arena"],
                    help="stage batches through a buffer-ring device arena "
                         "on a third pipeline stage (H2D overlaps training); "
                         "'arena' additionally assembles FE outputs directly "
                         "into the arena (zero-copy feed, no env->arena "
                         "memcpy) as per-field id vectors for the dedup'd "
                         "embedding feed")
    ap.add_argument("--mesh", default=None, metavar="PODSxDATA",
                    help="run the streaming train loop data-parallel on a "
                         "('pod', 'data') device mesh, e.g. 2x4, or 'auto' "
                         "to let elastic_remesh size the mesh from the "
                         "healthy device count (see --pod-size, --resume): "
                         "embedding "
                         "rows + Adagrad accumulators sharded over all "
                         "devices, two-stage (local->global) id dedup, "
                         "hierarchical cross-pod gradient reduction; "
                         "simulate devices with XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N "
                         "(streaming --data-dir mode, recsys only)")
    ap.add_argument("--compress", default="off",
                    choices=["bf16", "int8", "off"],
                    help="codec for the inter-pod gradient wire of --mesh "
                         "(error feedback carried in the optimizer state, "
                         "accumulation stays fp32); 'off' keeps the 1x1 "
                         "path bitwise-identical to single-device")
    ap.add_argument("--embedding", default="table",
                    choices=["table", "hierarchy"],
                    help="embedding backend: 'table' keeps the full table "
                         "in device memory; 'hierarchy' serves it from the "
                         "hierarchical PS (SSD memmap <- host LRU cache <- "
                         "per-batch working set) with the pull for batch "
                         "i+1 overlapping batch i's train step — tables "
                         "larger than device memory train end to end "
                         "(streaming --data-dir mode, recsys only)")
    ap.add_argument("--ps-dir", default=None,
                    help="directory for the hierarchical PS table file "
                         "(default: <data-dir>/_ps)")
    ap.add_argument("--host-cache-rows", type=int, default=100_000,
                    help="hierarchical PS host-DRAM cache capacity in rows")
    ap.add_argument("--device-budget-mb", type=float, default=None,
                    help="simulated device-memory budget: print whether the "
                         "PS table exceeds it (the beyond-HBM demo line)")
    ap.add_argument("--vocab-scale", type=float, default=1.0,
                    help="scale every sparse vocab by this factor (recsys): "
                         "grows the embedding table past any device budget "
                         "without changing the batch shapes")
    ap.add_argument("--adapt", default="fused", choices=["fused", "eager"],
                    help="spec->arch batch adaptation: 'fused' traces the "
                         "compiled ModelFeed plan inside the train step's "
                         "jit (one dispatch per step); 'eager' keeps the "
                         "legacy per-step jnp ops (the measurable baseline)")
    ap.add_argument("--no-donate", action="store_true",
                    help="do not donate params/optimizer/staged batch "
                         "through the jitted train step")
    ap.add_argument("--stream-workers", type=int, default=2)
    ap.add_argument("--stream-prefetch", type=int, default=4)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--n-hosts", type=int, default=1)
    # observability (repro.obs)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="export a Chrome trace-event / Perfetto timeline "
                         "of the run to PATH: loader readers, FE worker, "
                         "H2D feeder, and train loop as separate tracks "
                         "(open in ui.perfetto.dev or chrome://tracing)")
    ap.add_argument("--check", action="store_true",
                    help="preflight the run with repro.check (static plan "
                         "verifier, arena aliasing, jaxpr effects, lockset "
                         "audit) and refuse to train on error findings; "
                         "the report lands in the --metrics snapshot under "
                         "'check.*'")
    ap.add_argument("--metrics", action="store_true",
                    help="print the consolidated repro.obs.MetricsRegistry "
                         "snapshot (JSON) plus per-step HLO FLOPs / "
                         "HBM-bytes at exit (the HLO analysis costs one "
                         "extra compile)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.trace:
        from repro.obs.trace import enable_tracing
        enable_tracing()
    try:
        return _run(args)
    finally:
        if args.trace:
            from repro.obs.trace import get_tracer
            tracer = get_tracer()
            out = tracer.export(args.trace)
            print(f"trace: {len(out['traceEvents'])} events on "
                  f"{len(tracer.track_names())} tracks -> {args.trace}")


def _preflight(args, spec):
    """``--check``: run the static analyzers before touching any data.

    Returns the :class:`repro.check.Report` (registered under the
    ``check`` metrics tier) or raises ``SystemExit`` with the report's
    exit code on error findings / analyzer crashes — the 0/1/2 contract
    of ``python -m repro.check``.
    """
    from repro.check import run_check
    if spec.family != "recsys":
        raise SystemExit(
            f"--check verifies the FE feed pipeline, which only recsys "
            f"archs consume (got family={spec.family!r})")
    report = run_check(args.spec, args.arch)
    print(report.render())
    if report.exit_code:
        raise SystemExit(report.exit_code)
    return report


def _run(args) -> Dict[str, Any]:
    spec = get_arch(args.arch)
    if args.full and spec.config is None:
        raise SystemExit(f"{args.arch} has no single published config "
                         f"(it differs per shape)")
    cfg = spec.config if args.full else spec.smoke()
    if args.vocab_scale != 1.0:
        if spec.family != "recsys":
            raise SystemExit("--vocab-scale only applies to recsys archs")
        if args.vocab_scale <= 0:
            raise SystemExit("--vocab-scale must be > 0")
        import dataclasses
        cfg = dataclasses.replace(cfg, vocab_sizes=tuple(
            max(1, int(v * args.vocab_scale)) for v in cfg.vocab_sizes))
    if args.embedding == "hierarchy":
        if spec.family != "recsys":
            raise SystemExit(
                "--embedding hierarchy is a recsys embedding backend "
                f"(got family={spec.family!r})")
        if not args.data_dir:
            raise SystemExit(
                "--embedding hierarchy runs on the streaming pipeline: "
                "pass --data-dir (the PS pull is a pipeline stage)")
        if args.device_feed == "arena":
            raise SystemExit(
                "--embedding hierarchy is incompatible with --device-feed "
                "arena (the zero-copy arena assembles per-field id vectors "
                "for the in-memory dedup'd lookup); use on/off")
    if args.mesh:
        if spec.family != "recsys":
            raise SystemExit(
                "--mesh data-parallel training shards the embedding table "
                f"and is wired for recsys archs (got family={spec.family!r})")
        if not args.data_dir:
            raise SystemExit(
                "--mesh runs the streaming pipeline: pass --data-dir")
        if args.embedding == "hierarchy":
            raise SystemExit(
                "--mesh is incompatible with --embedding hierarchy (the PS "
                "pull path assumes a single device holds the working set); "
                "pick one scale-out axis")
    if (args.resume or args.fault_tolerant or args.chaos) and not args.data_dir:
        raise SystemExit(
            "--resume/--fault-tolerant/--chaos operate on the streaming "
            "ingest tier: pass --data-dir")
    key = jax.random.PRNGKey(0)
    opt = adamw(args.lr)
    check_report = _preflight(args, spec) if args.check else None
    if args.data_dir:
        # The streaming path builds its own boundary step: the working-set
        # capacity is tuned from the dataset manifest, and the step (table,
        # mesh or PS) decides the state's placement.
        return run_streaming(args, spec, cfg, key, opt,
                             check_report=check_report)

    if spec.family == "lm":
        from repro.models import transformer as T
        params = T.init_params(cfg, key)
        train_step = jax.jit(T.make_train_step(cfg, opt))
        opt_state = opt.init(params)
    elif spec.family == "recsys":
        from repro.models import recsys as R
        step_fn, init_st, _ = R.make_sparse_train_step(cfg, opt)
        train_step = jax.jit(step_fn)
        params, opt_state = R.init_train_state(cfg, key, init_st)
    else:
        from repro.models import gnn as G
        params = G.init_params(cfg, key)
        train_step = jax.jit(G.make_train_step(cfg, opt))
        opt_state = opt.init(params)

    state = {"params": params, "opt": opt_state}

    def step_wrapper(state, batch):
        p, o, m = train_step(state["params"], state["opt"], batch)
        return {"params": p, "opt": o}, m

    loop_cfg = LoopConfig(
        n_steps=args.steps,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
    )
    t0 = time.perf_counter()
    state, stats = run_training(
        cfg=loop_cfg,
        state=state,
        train_step=step_wrapper,
        batch_source=lambda s: synthetic_batch(spec.family, cfg, args.batch, s),
    )
    dt = time.perf_counter() - t0
    print(f"arch={args.arch} steps={stats.steps} "
          f"loss {stats.losses[0]:.4f} -> {stats.losses[-1]:.4f} "
          f"({dt:.1f}s, {dt/max(stats.steps,1)*1e3:.1f} ms/step)")
    if args.metrics:
        from repro.launch.hlo_stats import step_cost
        from repro.obs import MetricsRegistry
        reg = MetricsRegistry()
        reg.register("loop", stats)
        if check_report is not None:
            reg.register("check", check_report)
        tot = step_cost(train_step, state["params"], state["opt"],
                        synthetic_batch(spec.family, cfg, args.batch, 0))
        reg.register("hlo", tot)
        _print_hlo_cost(tot)
        print("metrics:")
        print(reg.to_json())
    if not all(math.isfinite(v) for v in stats.losses):
        raise FloatingPointError(f"non-finite loss in {stats.losses}")
    assert stats.losses[-1] < stats.losses[0], "training must reduce loss"
    return {"arch": args.arch, "config": cfg, "steps": stats.steps,
            "losses": list(stats.losses), "stats": stats}


if __name__ == "__main__":
    main()
