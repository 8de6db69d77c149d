"""Command-line interface (reference: root main.py + inference.py +
coordsim/main.py).

Subcommands:
- ``init-configs``: generate an example config set (agent/simulator/service/
  scheduler YAML + Abilene GraphML) — the assets the reference checks in
  under configs/, produced programmatically here.
- ``train``: load the 5 config namespaces, train DDPG, save an orbax
  checkpoint, then roll one greedy test episode on the inference network
  (main.py:16-76 flow).
- ``infer``: restore a checkpoint and run test episodes (inference.py:17-40).
- ``simulate``: standalone simulator smoke-run with a uniform dummy
  schedule, no RL (coordsim/main.py:19-89).
"""
from __future__ import annotations

import json
import os

import click
import jax
import numpy as np
import yaml


@click.group()
def cli():
    """gsc-tpu: TPU-native service coordination framework."""


# (temperature, floor) — the ONE definition behind the two
# --curriculum-* click defaults AND the flags-without-factory guard in
# train(): a tuned default must keep both in lockstep, or every
# non-factory run would trip the guard
_CURRICULUM_DEFAULTS = (1.0, 0.25)

def _uniform_schedule_action(limits, node_mask):
    """Flat [A] uniform dummy schedule over real nodes (the coordsim
    smoke-run placement, shared by `simulate` and `serve`'s request-pool
    roller)."""
    sched = np.zeros(limits.scheduling_shape, np.float32)
    sched[:, :, :, node_mask] = 1.0 / max(int(node_mask.sum()), 1)
    return sched.reshape(-1)


@cli.command("init-configs")
@click.option("--out", default="configs", show_default=True)
def init_configs(out: str):
    """Write an example config set (agent, simulator, service, scheduler,
    networks)."""
    from .topology.synthetic import (
        abilene,
        bteurope,
        claranet,
        compuserve,
        line,
        triangle,
        write_graphml,
    )

    os.makedirs(f"{out}/networks", exist_ok=True)
    write_graphml(abilene(), f"{out}/networks/abilene-in4.graphml")
    write_graphml(triangle(), f"{out}/networks/triangle.graphml")
    write_graphml(line(3), f"{out}/networks/line3.graphml")
    # ladder rung 3: 24-node/37-edge real topology (BT Europe, Topology Zoo)
    write_graphml(bteurope(node_cap_range=(1, 3)),
                  f"{out}/networks/bteurope-in2-rand-cap1-2.graphml")
    # the reference's other small real scenarios (Topology Zoo shapes)
    write_graphml(claranet(), f"{out}/networks/claranet-in4-cap1.graphml")
    write_graphml(compuserve(),
                  f"{out}/networks/compuserve-in4-cap1.graphml")

    with open(f"{out}/service_abc.yaml", "w") as f:
        yaml.safe_dump({
            "sfc_list": {"sfc_1": ["a", "b", "c"]},
            "sf_list": {n: {"processing_delay_mean": 5.0,
                            "processing_delay_stdev": 0.0}
                        for n in "abc"},
        }, f)
    # rung-3 5-SF chain with heterogeneous delays, a startup delay and a
    # non-identity resource function (reader.py:60-72 pluggable demand)
    with open(f"{out}/service_abcde.yaml", "w") as f:
        yaml.safe_dump({
            "sfc_list": {"sfc_1": ["a", "b", "c", "d", "e"]},
            "sf_list": {
                "a": {"processing_delay_mean": 5.0,
                      "processing_delay_stdev": 0.0},
                "b": {"processing_delay_mean": 2.0,
                      "processing_delay_stdev": 0.0},
                "c": {"processing_delay_mean": 10.0,
                      "processing_delay_stdev": 0.0,
                      "startup_delay": 5.0},
                "d": {"processing_delay_mean": 1.0,
                      "processing_delay_stdev": 0.0},
                "e": {"processing_delay_mean": 4.0,
                      "processing_delay_stdev": 0.0,
                      "resource_function_id": "overhead"},
            },
        }, f)
    with open(f"{out}/simulator.yaml", "w") as f:
        yaml.safe_dump({
            "inter_arrival_mean": 10.0, "deterministic_arrival": True,
            "flow_dr_mean": 1.0, "flow_dr_stdev": 0.0,
            "flow_size_shape": 0.001, "deterministic_size": True,
            "run_duration": 100, "ttl_choices": [100],
        }, f)
    # MMPP bursty-arrival scenario (rand-mmp-arrival12-8_det-size001_dur100)
    with open(f"{out}/simulator_mmpp.yaml", "w") as f:
        yaml.safe_dump({
            "inter_arrival_mean": 12.0, "deterministic_arrival": False,
            "flow_dr_mean": 1.0, "flow_dr_stdev": 0.0,
            "flow_size_shape": 0.001, "deterministic_size": True,
            "run_duration": 100, "ttl_choices": [100],
            "use_states": True, "init_state": "state_1",
            "states": {"state_1": {"inter_arr_mean": 12.0, "switch_p": 0.05},
                       "state_2": {"inter_arr_mean": 8.0, "switch_p": 0.05}},
        }, f)
    # trace-driven scenario (configs/traces format: time,node,
    # inter_arrival_mean[,cap] with popN node names, trace_processor.py:23-54)
    with open(f"{out}/trace_rampup.csv", "w") as f:
        f.write("time,node,inter_arrival_mean,cap\n")
        f.write("0,pop0,10.0,\n")
        f.write("500,pop0,5.0,\n")
        f.write("1000,pop0,2.5,4\n")
        f.write("1500,pop1,5.0,\n")
    with open(f"{out}/simulator_trace.yaml", "w") as f:
        yaml.safe_dump({
            "inter_arrival_mean": 10.0, "deterministic_arrival": True,
            "flow_dr_mean": 1.0, "flow_dr_stdev": 0.0,
            "flow_size_shape": 0.001, "deterministic_size": True,
            "run_duration": 100, "ttl_choices": [100],
            "trace_path": f"{out}/trace_rampup.csv",
        }, f)
    with open(f"{out}/agent.yaml", "w") as f:
        yaml.safe_dump({
            "observation_space": ["ingress_traffic", "node_load", "node_cap"],
            "graph_mode": True, "episode_steps": 200,
            "objective": "prio-flow", "target_success": "auto",
            "GNN_features": 22, "GNN_num_layers": 2, "GNN_num_iter": 2,
            "GNN_aggr": "mean",
            "actor_hidden_layer_nodes": [256],
            "critic_hidden_layer_nodes": [64],
            "mem_limit": 10000, "batch_size": 100,
            "nb_steps_warmup_critic": 200,
            "rand_mu": 0.0, "rand_sigma": 0.3,
            "gamma": 0.99, "target_model_update": 1.0e-4,
            "learning_rate": 1.0e-3,
        }, f)
    with open(f"{out}/scheduler.yaml", "w") as f:
        yaml.safe_dump({
            "training_network_files": [f"{out}/networks/abilene-in4.graphml"],
            "inference_network": f"{out}/networks/abilene-in4.graphml",
            "period": 10,
        }, f)
    click.echo(f"wrote example configs under {out}/")


def _build(agent_config, simulator_config, service, scheduler, seed,
           max_nodes, max_edges, resource_functions_path=None,
           precision=None, unroll=None, topo_mix=None):
    from .config.loader import load_agent, load_scheduler, load_service, load_sim
    from .config.schema import EnvLimits
    from .env.driver import EpisodeDriver
    from .env.env import ServiceCoordEnv

    # --precision overrides the agent yaml's (or default f32) policy
    agent = load_agent(agent_config,
                       **({"precision": precision} if precision else {}))
    # --unroll overrides the simulator yaml's scan_unroll (`is not None`,
    # not truthiness: an explicit --unroll 0 must reach SimConfig
    # validation and ERROR, never silently keep the yaml value)
    sim_overrides = {}
    if unroll is not None:
        sim_overrides["scan_unroll"] = unroll
    sim_cfg = load_sim(simulator_config, **sim_overrides)
    svc = load_service(service,
                       resource_functions_path=resource_functions_path)
    sched = load_scheduler(scheduler)
    limits = EnvLimits.for_service(svc, max_nodes=max_nodes,
                                   max_edges=max_edges)
    env = ServiceCoordEnv(svc, sim_cfg, agent, limits)
    driver = EpisodeDriver(sched, sim_cfg, svc, agent.episode_steps,
                           max_nodes=max_nodes, max_edges=max_edges,
                           base_seed=seed, topo_mix=topo_mix)
    return env, driver, agent


@cli.command()
@click.argument("agent_config")
@click.argument("simulator_config")
@click.argument("service")
@click.argument("scheduler")
@click.option("--episodes", default=40, show_default=True)
@click.option("--seed", default=0, show_default=True)
@click.option("--result-dir", default="results", show_default=True)
@click.option("--experiment-id", default=None)
@click.option("--max-nodes", default=24, show_default=True)
@click.option("--max-edges", default=37, show_default=True)
@click.option("--tensorboard/--no-tensorboard", default=False)
@click.option("--profile/--no-profile", default=False,
              help="write a jax profiler trace of training")
@click.option("--runs", default=1, show_default=True,
              help="independent seeded runs; the best by mean reward over "
                   "the last 10 episodes is reported (select_best_agent)")
@click.option("--resume", default=None,
              help="checkpoint dir from a previous train run: restores "
                   "params+opt+targets+replay+PRNG and continues exactly "
                   "(total episode count still set by --episodes).  "
                   "'auto' searches --result-dir for the newest checkpoint "
                   "whose content checksum validates (periodic/preemption "
                   "saves and final checkpoints all qualify), falling back "
                   "past corrupted ones")
@click.option("--resource-functions-path", default=None,
              help="dir (or .py file) of user resource-function plugins "
                   "to register before parsing the service catalog "
                   "(reference: reader.py:60-72 dynamic imports)")
@click.option("--replicas", default=1, show_default=True,
              help="vmapped env replicas per episode (>1: the TPU "
                   "data-parallel path with on-device per-episode traffic "
                   "sampling; 1: the reference's single-env loop)")
@click.option("--chunk", default=50, show_default=True,
              help="rollout steps per device call with --replicas > 1; "
                   "must divide episode_steps.  Shorter calls hand control "
                   "back to the host more often; a whole 200-step episode "
                   "in one call also runs on the v5e")
@click.option("--mesh", default=None,
              help="pjit device mesh 'DPxMP' (e.g. 8x1, 4x2) for "
                   "--replicas > 1: env replicas/replay/traffic shard "
                   "over the dp*mp device grid and the learner state "
                   "follows --partition-rules.  Replica count must be "
                   "divisible by dp*mp.  The backend must HAVE dp*mp "
                   "devices (for a CPU dry run preset JAX_PLATFORMS=cpu "
                   "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                   "— nothing re-platforms a run).  Checkpoints are always "
                   "host-gathered, so a "
                   "--resume may use a DIFFERENT mesh shape than the run "
                   "that wrote them (elastic resume).  Unset: today's "
                   "single-device dispatch")
@click.option("--partition-rules", type=click.Choice(["replicated",
                                                      "sharded", "tp"]),
              default="replicated", show_default=True,
              help="partition rulebook for the learner state under "
                   "--mesh: 'replicated' keeps every parameter on every "
                   "device (bit-identical to 'sharded' on the same mesh; "
                   "a 1x1 mesh is bit-identical to no --mesh at all, a "
                   "multi-device mesh drifts ~1e-7 vs the meshless "
                   "dispatch from fusion-boundary reordering), 'sharded' "
                   "splits "
                   "wide actor/critic/GAT matrices + their Adam moments "
                   "over the mp axis (parallel.partition.sharded_rules) "
                   "— final learner state stays bit-identical across "
                   "mesh carvings of the same device count.  'tp' is "
                   "TRUE tensor-parallel compute "
                   "(parallel.partition.tp_rules): contraction dims "
                   "split over mp with psum-accumulated partial "
                   "products, the state stays resident-sharded THROUGH "
                   "the compiled program (no entry/exit layout moves) — "
                   "results drift ~1e-7/mp per gradient step and are "
                   "accepted by the bench_diff learning-curve envelope "
                   "vs a replicated control, NOT by bit-equality")
@click.option("--topo-mix", default=None,
              help="mixed-topology batched training (--replicas > 1): "
                   "fill the replica axis with a round-robin of this "
                   "comma-separated mix instead of one network per "
                   "episode.  Entries: 'schedule' (expands to the "
                   "scheduler's training topologies) or a scenario-"
                   "registry name (abilene, triangle, bteurope, ..., "
                   "random<N>/star<N>/ring<N>/line<N>), each optionally "
                   "'+<shape>' (bursty|diurnal|flash_crowd traffic), "
                   "'~<site>@<interval>[.<index>]' capacity faults "
                   "(link/node, '&'-joined), ':<seed>' (randomized "
                   "generators only).  Example: "
                   "'schedule,abilene+bursty,random12~link@3.0:7'.  One "
                   "compiled program serves the whole mixture — the "
                   "schedule 'switch' is just per-replica topology data, "
                   "so nothing retraces.  OR the on-device scenario "
                   "factory: 'factory:<fam>[-<fam>...][+shapes][~faults]' "
                   "(families star/ring/line/random, or 'all') samples a "
                   "fresh randomized per-replica (topology, traffic, "
                   "fault plan) INSIDE the compiled program every "
                   "episode — zero host regen, zero retraces, an "
                   "unbounded scenario distribution — with batch "
                   "composition steered by the TD auto-curriculum "
                   "(--curriculum-temperature/--curriculum-floor)")
@click.option("--pipeline/--no-pipeline", default=True, show_default=True,
              help="asynchronous episode pipeline (--replicas 1 path): "
                   "background traffic prefetch, fused rollout+learn "
                   "device step, deferred metric draining — bit-identical "
                   "results, the chip never idles between episodes; "
                   "--no-pipeline runs the serial reference loop")
@click.option("--precision", type=click.Choice(["f32", "bf16"]),
              default=None,
              help="dtype policy override: f32 (default; bit-identical to "
                   "the dtype-unaware stack) or bf16 (mixed-precision "
                   "network compute + replay storage with f32 master "
                   "params/optimizer/TD targets — ~2x MXU throughput, "
                   "half the replay HBM).  Unset = the agent yaml's "
                   "'precision' key (default f32)")
@click.option("--unroll", type=int, default=None,
              help="substep-scan unroll factor override "
                   "(SimConfig.scan_unroll; trades compile time for less "
                   "scan overhead on the op-count-bound substep).  Unset "
                   "= the simulator yaml's 'scan_unroll' key (default 1)")
@click.option("--obs/--no-obs", "obs_enabled", default=True,
              show_default=True,
              help="unified run telemetry: per-episode events.jsonl "
                   "(SPS, phase timings, losses/grad-norms, drop reasons, "
                   "device memory), atomic metrics.json snapshots, and "
                   "the pipeline watchdog — tools/obs_report.py renders "
                   "the stream")
@click.option("--obs-dir", default=None,
              help="directory for events.jsonl/metrics.json "
                   "(default: the run's result dir)")
@click.option("--obs-interval", default=10, show_default=True,
              help="episodes between atomic metrics.json snapshot "
                   "rewrites")
@click.option("--obs-rotate-mb", default=0.0, show_default=True,
              help="size-based events.jsonl rotation for long exhibits: "
                   "when the live stream exceeds this many MiB it rotates "
                   "to events.jsonl.1..N (readers — obs_report, the trace "
                   "exporter — walk the segments transparently; 0 = no "
                   "rotation)")
@click.option("--obs-series-window", default=1024, show_default=True,
              help="flight recorder: points kept per metric in the hub's "
                   "bounded time-series rings (drop-oldest).  Feeds the "
                   "whole-run series.json, the /series endpoint query, "
                   "the async pipeline trace tracks and the black-box "
                   "post-mortem dumps.  0 disables history entirely — "
                   "the event stream is then byte-identical to a "
                   "recorder-free run")
@click.option("--perf/--no-perf", "perf_enabled", default=True,
              show_default=True,
              help="device-cost ledger: capture compiled FLOPs/bytes/"
                   "fusion counts of the watched entry points at compile "
                   "time, merge the run's phase wall into per-dispatch "
                   "MFU/roofline, and write perf.json next to "
                   "metrics.json (tools/bench_diff.py diffs them across "
                   "runs).  Costs one extra AOT trace per entry point at "
                   "startup; adds nothing to the dispatch path")
@click.option("--learn-obs/--no-learn-obs", "learnobs_enabled",
              default=True, show_default=True,
              help="on-device learning-signal ledger: per-topology "
                   "|TD-error| segments (segment_sum over the replay "
                   "rows' topo_idx), Q-value distribution moments, "
                   "per-layer param/grad norms and replay fill/age — "
                   "computed INSIDE the dispatched programs and drained "
                   "with the deferred metric drain (zero new host "
                   "syncs).  Lands as learn_signal events + tagged "
                   "gauges; RunObserver.close() extracts schema-"
                   "versioned curves.json that tools/bench_diff.py "
                   "gates (final-window return, AUC, episodes-to-"
                   "threshold)")
@click.option("--metrics-port", default=0, show_default=True,
              help="live Prometheus /metrics endpoint over the run's "
                   "MetricsHub (stdlib HTTP server on 127.0.0.1) so a "
                   "long run can be scraped WHILE it executes: curl "
                   "http://127.0.0.1:<port>/metrics.  0 = disabled; the "
                   "bound port is recorded as a metrics_endpoint event")
@click.option("--watchdog-budget", default=300.0, show_default=True,
              help="seconds without a completed episode before the "
                   "pipeline watchdog emits a structured 'stall' event "
                   "(0 disables the watchdog)")
@click.option("--watchdog-escalate", default=3, show_default=True,
              help="after the first stall, this many MORE full "
                   "--watchdog-budget periods of continued silence "
                   "escalate from reporting to acting: the watchdog "
                   "interrupts the prefetcher and the trainer restarts it "
                   "from the episode counter (0 = report-only)")
@click.option("--check-invariants/--no-check-invariants", default=False,
              show_default=True,
              help="run utils.debug.check_invariants on every drained "
                   "episode's final simulator state; violations emit "
                   "structured 'invariant_violation' events")
@click.option("--fault-plan", default=None,
              help="deterministic fault injection for chaos testing "
                   "(resilience.FaultPlan grammar: 'site@key[:arg]' "
                   "joined by ';').  Serial sites key by episode: "
                   "prefetch_die, slow_episode, dispatch_transient, "
                   "nan_grads, ckpt_corrupt.  Async fleet sites "
                   "(--async): actor_die@a<actor>:<episode>, "
                   "ring_poison@<episode>, publish_corrupt@v<version>, "
                   "watcher_stall@a<actor>:<episode>[:sleep_s], "
                   "learner_transient@<burst>.  nan_grads also fires on "
                   "--replicas > 1 (host-verified, rollback-backed).  "
                   "Unset: the GSC_FAULT_PLAN env var; empty = no faults")
@click.option("--rollback/--no-rollback", default=True, show_default=True,
              help="keep a last-good in-memory snapshot of (state, "
                   "replay) and roll back when the on-device all-finite "
                   "guard flags a poisoned learner state (costs ~2 extra "
                   "replay copies in HBM; training math is bit-identical "
                   "until a violation actually triggers)")
@click.option("--ckpt-interval", default=0, show_default=True,
              help="episodes between preemption-safe checkpoints "
                   "(checksummed, written under <run>/ckpts with a "
                   "rotating last-good pointer; 0 disables).  SIGTERM/"
                   "SIGINT always snapshot one on the way out")
@click.option("--ckpt-retain", default=3, show_default=True,
              help="periodic checkpoints kept on disk (the last-good "
                   "pointer target is never pruned)")
@click.option("--hot-swap-dir", default=None,
              help="train-while-serve: publish the actor params as "
                   "versioned, fingerprint-keyed hot-swap artifacts "
                   "(serve.fleet.WeightPublisher) into this directory "
                   "every --publish-interval drained-finite episodes — a "
                   "concurrently running `cli serve --hot-swap-dir` "
                   "fleet swaps each version in between dispatches.  "
                   "--replicas 1 ships the rollback guard's VERIFIED "
                   "snapshot; --replicas > 1 ships the host-gathered, "
                   "finite-verified replica state (mesh-agnostic layout "
                   "under --mesh, like the checkpoints)")
@click.option("--publish-interval", default=1, show_default=True,
              help="episodes between hot-swap weight publishes "
                   "(with --hot-swap-dir)")
@click.option("--async", "async_mode", is_flag=True, default=False,
              help="decoupled actor/learner training (--replicas > 1): "
                   "--async-actors rollout threads run the jitted replica "
                   "rollout continuously and ship device-resident "
                   "transition blocks into the shared replay ring (one "
                   "jitted replay_ingest per block, no host round-trip), "
                   "while the learner runs learn bursts back-to-back and "
                   "publishes actor weights every --publish-bursts bursts "
                   "over an in-process WeightPublisher bus the actors "
                   "adopt between dispatches.  Off-policy staleness is "
                   "bounded (--max-staleness) and measured (policy_lag / "
                   "replay_lag gauges, actor_idle/learner_idle phases).  "
                   "Composes with --mesh over the dp axis: the replay "
                   "ring lives dp-sharded on the learner mesh, ingest is "
                   "an AOT-compiled per-shard donated write (asserted "
                   "collective-free) and learn bursts run under the full "
                   "pjit plan (tp-only meshes, dp=1, are refused).  "
                   "Composes with --fault-plan (async fleet sites; actor "
                   "supervision + poison quarantine + rollback) and with "
                   "--resume auto after a SIGTERM preemption; learning "
                   "curves match the sync control within bench_diff's "
                   "curve bands, not bit-exactly")
@click.option("--async-actors", default=2, show_default=True,
              help="rollout threads for --async (each owns its own env "
                   "replicas batch, PRNG stream and adopted weights; "
                   "episodes are round-robined by global index, so the "
                   "scenario stream is thread-count-independent)")
@click.option("--max-staleness", default=0, show_default=True,
              help="--async backpressure bound: max produced-but-"
                   "uningested env steps the actors may run ahead of the "
                   "learner before the replay channel blocks them "
                   "(0 = two episodes' worth per actor)")
@click.option("--publish-bursts", default=1, show_default=True,
              help="learn bursts between actor-weight publishes on the "
                   "--async path (higher = staler actors, fewer "
                   "publish-time host syncs)")
@click.option("--learn-ratio", default=1.0, show_default=True,
              help="--async learner pacing: gradient-step budget per "
                   "ingested env step, relative to the sync control "
                   "(1.0 = one burst per replicas*episode_steps ingested "
                   "steps — the matched-budget setting the curve bands "
                   "assume)")
@click.option("--curriculum-temperature", default=_CURRICULUM_DEFAULTS[0],
              show_default=True,
              help="TD auto-curriculum softmax temperature over the "
                   "per-family |TD| EWMAs (factory --topo-mix only): "
                   "lower = chase the generalization frontier harder, "
                   "higher = flatter; infinity degenerates to "
                   "round-robin-like uniform sampling")
@click.option("--curriculum-floor", default=_CURRICULUM_DEFAULTS[1],
              show_default=True,
              help="total probability mass the auto-curriculum always "
                   "spreads uniformly over the factory families (0..1): "
                   "no family's sampling probability can fall below "
                   "floor/K, so every family stays alive (forgetting "
                   "stays visible)")
@click.option("--verbose/--quiet", default=True)
def train(agent_config, simulator_config, service, scheduler, episodes, seed,
          result_dir, experiment_id, max_nodes, max_edges, tensorboard,
          profile, runs, resume, resource_functions_path, replicas, chunk,
          mesh, partition_rules, topo_mix, pipeline, precision,
          unroll, obs_enabled, obs_dir, obs_interval,
          obs_rotate_mb, obs_series_window, perf_enabled,
          learnobs_enabled, metrics_port,
          watchdog_budget, watchdog_escalate,
          check_invariants, fault_plan, rollback, ckpt_interval,
          ckpt_retain, hot_swap_dir, publish_interval, async_mode,
          async_actors, max_staleness, publish_bursts, learn_ratio,
          curriculum_temperature, curriculum_floor, verbose):
    """Train DDPG, checkpoint, then one greedy test episode
    (main.py:16-76).  With --runs N, trains N seeds and selects the best
    (src/rlsp/agents/main.py:89-113 semantics).  With --replicas B, each
    episode rolls out B vmapped env replicas feeding sharded replay — the
    TPU scale-out the reference lacks; evaluation and the checkpointed
    learner state are identical in shape to the single-env path."""
    import numpy as _np

    from .agents.trainer import Trainer
    from .utils.checkpoint import load_checkpoint, save_checkpoint
    from .utils.experiment import (
        ExperimentResult,
        copy_inputs,
        select_best_agent,
        setup_result_dir,
    )

    from .runtime import (device_summary, enable_compile_cache,
                          tree_platforms)
    jax_cache_dir = enable_compile_cache()
    if resume and runs != 1:
        raise click.BadParameter("--resume only supports --runs 1")
    if metrics_port < 0:
        raise click.BadParameter("--metrics-port must be >= 0 "
                                 "(0 = disabled)")
    if metrics_port and not obs_enabled:
        # same contract as cli serve: a port that silently never binds
        # would leave a scraper on connection-refused all run long
        raise click.BadParameter("--metrics-port needs the run observer "
                                 "(drop --no-obs)")
    if unroll is not None and unroll < 1:
        # fail fast with the flag's name, not a SimConfig traceback from
        # deep inside the run loop
        raise click.BadParameter("--unroll must be a positive integer")
    if publish_interval < 1:
        raise click.BadParameter("--publish-interval must be >= 1")
    if async_mode:
        # fail fast with the flag's name — the trainer raises the same
        # refusals, but from deep inside the run loop after the build
        if replicas <= 1:
            raise click.BadParameter(
                "--async decouples the replica rollout from the learner "
                "— it requires the replica-parallel path (--replicas > 1)")
        if async_actors < 1:
            raise click.BadParameter("--async-actors must be >= 1")
        if max_staleness < 0:
            raise click.BadParameter(
                "--max-staleness must be >= 0 (0 = two episodes' worth "
                "of steps per actor)")
        if publish_bursts < 1:
            raise click.BadParameter("--publish-bursts must be >= 1")
        if learn_ratio <= 0:
            raise click.BadParameter("--learn-ratio must be > 0")
    elif (async_actors, max_staleness, publish_bursts, learn_ratio) != \
            (2, 0, 1, 1.0):
        raise click.BadParameter(
            "--async-actors/--max-staleness/--publish-bursts/"
            "--learn-ratio tune the decoupled actor/learner path — pass "
            "--async or drop the flags")
    plan = None
    if mesh:
        # build the plan BEFORE any other jax work so the mesh binds the
        # backend's first-created devices
        from .parallel import ShardingPlan, parse_mesh_shape
        if replicas <= 1:
            raise click.BadParameter(
                "--mesh shards env replicas over the device grid — it "
                "requires the replica-parallel path (--replicas > 1)")
        try:
            dp_, mp_ = parse_mesh_shape(mesh)
        except ValueError as e:
            raise click.BadParameter(str(e))
        if replicas % (dp_ * mp_) != 0:
            raise click.BadParameter(
                f"--replicas ({replicas}) must be divisible by the mesh "
                f"device count ({dp_ * mp_} = {dp_}x{mp_}) for an even "
                "replica sharding")
        try:
            # make_train_mesh raises when the backend is short of devices
            plan = ShardingPlan.from_spec(mesh, rules=partition_rules)
        except ValueError as e:
            raise click.UsageError(f"--mesh {mesh}: {e}")
        if async_mode:
            # dp-sharded replay needs a dp axis — refuse tp-only grids
            # here with the flag's name, not from inside the run loop
            try:
                plan.assert_async_capable()
            except ValueError as e:
                raise click.BadParameter(str(e))
    elif partition_rules != "replicated":
        raise click.BadParameter(
            f"--partition-rules {partition_rules} has no effect without "
            "--mesh — pass --mesh DPxMP (e.g. 4x2) or drop the flag")
    if topo_mix:
        if replicas <= 1:
            raise click.BadParameter(
                "--topo-mix fills the replica axis with the mixture — it "
                "requires the replica-parallel path (--replicas > 1)")
        # grammar + registry-name validation BEFORE any expensive build
        # (factory: entries parse through topology.factory, everything
        # else through the registry); size/fit errors (a 53-node tinet
        # in a 24-node bucket) surface from the driver's compile with
        # the bucket dims in the message
        from .topology.scenarios import validate_mix
        try:
            validate_mix(topo_mix)
        except ValueError as e:
            raise click.BadParameter(f"--topo-mix: {e}")
    from .topology.factory import is_factory_mix
    curriculum_cfg = None
    if is_factory_mix(topo_mix):
        from .env.curriculum import CurriculumConfig
        try:
            curriculum_cfg = CurriculumConfig(
                temperature=curriculum_temperature,
                floor=curriculum_floor)
        except ValueError as e:
            raise click.BadParameter(str(e))
    elif (curriculum_temperature, curriculum_floor) != _CURRICULUM_DEFAULTS:
        raise click.BadParameter(
            "--curriculum-* steers the on-device scenario factory — "
            "pass --topo-mix factory:... or drop the flags")
    if resume == "auto":
        # newest checksummed checkpoint under the result root that still
        # validates — a corrupted newest (half-written at the kill, bit
        # rot) falls back to the previous good one
        from .resilience.ckpt import find_resumable
        found = find_resumable(result_dir)
        if not found:
            raise click.BadParameter(
                "--resume auto: no checkpoint with a validating content "
                f"checksum under {result_dir!r} (periodic --ckpt-interval "
                "saves, preemption snapshots and final checkpoints all "
                "qualify)")
        click.echo(f"[resume auto] {found}", err=True)
        resume = found
    # deterministic chaos schedule (--fault-plan / GSC_FAULT_PLAN env);
    # parse errors must fail the command before any run state exists.
    # Parsed FRESH per run below — FaultPlan specs fire exactly once, so
    # one shared object would leave runs 1..N-1 silently fault-free.
    from .resilience.faults import FaultPlan
    try:
        FaultPlan.from_env(fault_plan)
    except ValueError as e:
        raise click.BadParameter(str(e))
    run_dirs = []
    outputs = {}
    for run in range(runs):
        fplan = FaultPlan.from_env(fault_plan)
        run_seed = seed + run
        if resume:
            # the checkpoint records the precision it was trained under
            # (sidecar meta): silently rebuilding its bf16 replay into an
            # f32 template (or vice versa) would either round the buffer
            # or drop it behind a misleading format-mismatch fallback —
            # adopt the recorded policy, and refuse a contradicting flag
            from .utils.checkpoint import read_checkpoint_meta
            meta = read_checkpoint_meta(resume)
            # a checkpoint without the sidecar predates the precision
            # policy and can only hold f32 state/replay — treating it as
            # anything else would rebuild a mismatched replay template
            # and drop the stored buffer behind the format-fallback path
            ck_prec = meta.get("precision") or "f32"
            if precision and precision != ck_prec:
                raise click.BadParameter(
                    f"--precision {precision} contradicts the checkpoint's "
                    f"{'recorded' if 'precision' in meta else 'implicit pre-meta'} "
                    f"policy ({ck_prec}); resume adopts the checkpoint's "
                    "precision — drop the flag or retrain")
            if not precision and ck_prec != "f32":
                click.echo(f"[resume] adopting checkpoint precision "
                           f"{ck_prec}", err=True)
            precision = ck_prec
        rdir = setup_result_dir(result_dir, experiment_id)
        run_dirs.append(rdir)
        copy_inputs(rdir, [agent_config, simulator_config, service, scheduler])
        result = ExperimentResult(rdir)
        result.env_config = {"agent_config": agent_config,
                             "simulator_config": simulator_config,
                             "service": service, "scheduler": scheduler,
                             "seed": run_seed}
        # console + per-run file log (setup_logging, main.py:307-329)
        from .utils.logging import setup_logging
        setup_logging(verbose=False, logfile=os.path.join(rdir, "run.log"))
        env, driver, agent = _build(agent_config, simulator_config, service,
                                    scheduler, run_seed, max_nodes, max_edges,
                                    resource_functions_path,
                                    precision=precision,
                                    unroll=unroll, topo_mix=topo_mix)
        # episode-0 topology/traffic memo: mesh_meta and the resume
        # template both need the same deterministic build, and it is
        # real host work — pay it at most once per run
        _ep0 = []

        def _episode0():
            if not _ep0:
                _ep0.append(driver.episode(0, False))
            return _ep0[0]

        mesh_meta = {}
        if plan is not None and obs_enabled:
            # partition-layout record for run_start: the effective mesh
            # shape + per-leaf spec counts (never the full tree) over the
            # eval_shape'd learner state — pure tracing, no device work,
            # and the SAME summary() the tests assert on.  Gated on obs:
            # run_start is its only consumer, and the episode(0) traffic
            # build is real host work a --no-obs run shouldn't pay
            from .agents.ddpg import DDPG as _DDPG
            topo0, traffic0 = _episode0()
            _, obs_shape = jax.eval_shape(
                env.reset, jax.random.PRNGKey(0), topo0, traffic0)
            state_shape = jax.eval_shape(
                _DDPG(env, agent).init, jax.random.PRNGKey(0), obs_shape)
            mesh_meta = {"mesh": plan.describe(),
                         "partition_rules": partition_rules,
                         "partition_specs": plan.summary(state_shape)}
        obs = None
        if obs_enabled:
            from .obs import RunObserver

            # with --runs N and an explicit --obs-dir, each run gets its
            # own subdirectory so the event streams never interleave
            odir = obs_dir or rdir
            if obs_dir and runs > 1:
                odir = os.path.join(obs_dir, f"run{run}")
            obs = RunObserver(odir, snapshot_interval=obs_interval,
                              watchdog_budget_s=watchdog_budget,
                              watchdog_escalate=watchdog_escalate,
                              rotate_mb=obs_rotate_mb, perf=perf_enabled,
                              learn=learnobs_enabled,
                              metrics_port=(metrics_port or None),
                              series_window=obs_series_window,
                              tags={"seed": run_seed})
            obs.start(meta={"episodes": episodes, "replicas": replicas,
                            "pipeline": pipeline, "seed": run_seed,
                            "topo_mix": topo_mix,
                            **({"curriculum": {
                                "temperature": curriculum_temperature,
                                "floor": curriculum_floor}}
                               if curriculum_cfg is not None else {}),
                            "precision": agent.precision,
                            # the EFFECTIVE unroll (yaml or flag), read
                            # back from the built sim_cfg so the recorded
                            # value can't drift from what ran
                            "unroll": env.sim_cfg.scan_unroll,
                            "result_dir": rdir,
                            "ckpt_interval": ckpt_interval,
                            "hot_swap_dir": hot_swap_dir,
                            **({"async": {
                                "actors": async_actors,
                                "max_staleness": max_staleness,
                                "publish_bursts": publish_bursts,
                                "learn_ratio": learn_ratio}}
                               if async_mode else {}),
                            "jax_cache_dir": jax_cache_dir,
                            **mesh_meta,
                            **({"fault_plan": fplan.summary()} if fplan
                               else {})})
        trainer = Trainer(env, driver, agent, seed=run_seed, result_dir=rdir,
                          tensorboard=tensorboard, obs=obs,
                          check_invariants=check_invariants,
                          fault_plan=fplan, rollback=rollback)
        # checksummed rotating checkpoints under the run dir: periodic
        # (--ckpt-interval) and the SIGTERM/SIGINT snapshot both land
        # here, which is exactly the tree --resume auto searches
        from .resilience.ckpt import CheckpointManager
        from .resilience.preempt import PreemptionGuard
        manager = CheckpointManager(os.path.join(rdir, "ckpts"),
                                    retain=ckpt_retain,
                                    meta={"precision": agent.precision},
                                    fault_plan=fplan, obs=obs)
        try:
            # everything from here on runs under the observer: a failed
            # resume restore (or bad --episodes) must still land the
            # run_end status=error tail before propagating
            init_state = init_buffer = None
            start_episode = 0
            if resume:
                from .utils.checkpoint import load_full_or_partial
                topo0, traffic0 = _episode0()
                _, obs0 = env.reset(jax.random.PRNGKey(0), topo0, traffic0)
                example = trainer.ddpg.init(jax.random.PRNGKey(0), obs0)
                if replicas > 1:
                    # replica-sharded replay: [B, capacity, ...] leaves — a
                    # checkpoint from a matching --replicas run restores
                    # fully; anything else falls back to state-only
                    from .parallel import ParallelDDPG
                    example_buffer = ParallelDDPG(
                        env, agent, num_replicas=replicas).init_buffers(obs0)
                else:
                    example_buffer = trainer.ddpg.init_buffer(obs0)
                restored, buffer_ok = load_full_or_partial(
                    resume, example, example_buffer=example_buffer,
                    example_extra={"episode": _np.asarray(0, _np.int32)})
                if buffer_ok:
                    init_buffer = restored["buffer"]
                else:
                    init_buffer = None
                    click.echo("[resume] replay buffer not restorable "
                               "(legacy storage format, or replay config "
                               "such as mem_limit changed since the "
                               "checkpoint) — restored state only, replay "
                               "starts empty", err=True)
                init_state = restored["state"]
                start_episode = int(restored["extra"]["episode"]) \
                    if "extra" in restored else 0
                if start_episode >= episodes:
                    # range(start, episodes) would be empty: no training,
                    # but the checkpoint would be REWRITTEN with the
                    # smaller counter — corrupting exact resume for later
                    # runs
                    raise click.BadParameter(
                        f"--episodes ({episodes}) must exceed the "
                        f"checkpoint's completed episode count "
                        f"({start_episode})")
            result.runtime_start("train")
            # SIGTERM/SIGINT during training stop the loop at the next
            # episode boundary; the snapshot + clean exit happen below
            with PreemptionGuard() as guard:
                publisher = None
                if hot_swap_dir:
                    from .serve.fleet import WeightPublisher
                    publisher = WeightPublisher(
                        hot_swap_dir,
                        hub=(obs.hub if obs is not None else None),
                        fault_plan=fplan)
                if replicas > 1 and async_mode:
                    state, buffer = trainer.train_async(
                        episodes, num_replicas=replicas, chunk=chunk,
                        actor_threads=async_actors,
                        verbose=verbose, profile=profile,
                        init_state=init_state, init_buffers=init_buffer,
                        start_episode=start_episode,
                        ckpt_manager=manager, ckpt_interval=ckpt_interval,
                        preempt=guard, plan=plan, publisher=publisher,
                        publish_bursts=publish_bursts,
                        curriculum=curriculum_cfg,
                        max_staleness=max_staleness,
                        learn_ratio=learn_ratio)
                elif replicas > 1:
                    state, buffer = trainer.train_parallel(
                        episodes, num_replicas=replicas, chunk=chunk,
                        verbose=verbose, profile=profile,
                        init_state=init_state, init_buffers=init_buffer,
                        start_episode=start_episode,
                        ckpt_manager=manager, ckpt_interval=ckpt_interval,
                        preempt=guard, plan=plan, publisher=publisher,
                        publish_interval=(publish_interval
                                          if hot_swap_dir else 0),
                        curriculum=curriculum_cfg)
                else:
                    state, buffer = trainer.train(
                        episodes, verbose=verbose, profile=profile,
                        init_state=init_state, init_buffer=init_buffer,
                        start_episode=start_episode, pipeline=pipeline,
                        ckpt_manager=manager, ckpt_interval=ckpt_interval,
                        preempt=guard, publisher=publisher,
                        publish_interval=(publish_interval
                                          if hot_swap_dir else 0))
            result.runtime_stop("train")

            if trainer.preempted:
                # preemption-safe exit: a checksummed snapshot of the
                # drained state (monotone episode counter), a clean rc=0,
                # and a JSON line saying how to continue — no evaluation,
                # the grace window is for the checkpoint
                done = trainer.completed_episodes
                ckpt = manager.save(state, buffer, episode=done)
                if obs is not None:
                    obs.close(status="preempted")
                result.metrics = {"status": "preempted"}
                result.write()
                payload = {
                    "status": "preempted", "signal": guard.signame,
                    "result_dir": rdir, "checkpoint": ckpt,
                    "episodes_completed": done,
                    "hint": "continue with --resume auto"}
                ainfo = getattr(trainer, "async_info", None)
                if async_mode and ainfo:
                    # the ASYNC_r02 drain proof, attached to the exit
                    # line: a preempted async run must have drained the
                    # channel fully before the snapshot above
                    payload["drain"] = {
                        k: ainfo[k] for k in (
                            "produced_steps", "ingested_steps",
                            "transitions_lost")
                        if k in ainfo}
                click.echo(json.dumps(payload))
                return

            ckpt = save_checkpoint(os.path.join(rdir, "checkpoint"), state,
                                   buffer=buffer,
                                   extra={"episode": _np.asarray(episodes,
                                                                 _np.int32)},
                                   meta={"precision": agent.precision,
                                         "episode": episodes},
                                   checksum=True)
            result.runtime_start("test")
            test = trainer.evaluate(state, episodes=1, test_mode=True,
                                    telemetry=True)
            result.runtime_stop("test")
        except BaseException:
            # the run's final events (run_end status=error + a last
            # snapshot) must land even when training faults — that tail
            # is exactly what post-mortems read.  Best effort: a close
            # that itself fails (e.g. the same full disk that killed the
            # run) must not mask the original traceback.
            if obs is not None:
                try:
                    obs.close(status="error")
                except Exception:
                    pass
            raise
        if obs is not None:
            obs.close(status="ok")
        result.metrics = test
        result.write()
        outputs[rdir] = {"result_dir": rdir, "checkpoint": ckpt, **test,
                         "device": device_summary(),
                         # where the trained learner state lives when the
                         # loop ends ("host" = gathered numpy, the --mesh
                         # layout) — a device run that fell back to the
                         # CPU shows here
                         "state_platforms": tree_platforms(state)}
    best = select_best_agent(run_dirs) if runs > 1 else run_dirs[0]
    click.echo(json.dumps({**outputs[best], "runs": runs,
                           "all_result_dirs": run_dirs}))


@cli.command()
@click.argument("agent_config")
@click.argument("simulator_config")
@click.argument("service")
@click.argument("scheduler")
@click.argument("checkpoint")
@click.option("--episodes", default=1, show_default=True)
@click.option("--seed", default=0, show_default=True)
@click.option("--max-nodes", default=24, show_default=True)
@click.option("--max-edges", default=37, show_default=True)
@click.option("--resource-functions-path", default=None,
              help="dir (or .py file) of user resource-function plugins")
@click.option("--precision", type=click.Choice(["f32", "bf16"]),
              default=None,
              help="dtype policy override; unset = the checkpoint's "
                   "recorded policy (sidecar meta; falls back to the "
                   "agent yaml for pre-meta checkpoints) so the greedy "
                   "episodes evaluate under the compute dtype the "
                   "checkpoint was trained with")
def infer(agent_config, simulator_config, service, scheduler, checkpoint,
          episodes, seed, max_nodes, max_edges, resource_functions_path,
          precision):
    """Restore a checkpoint and run greedy test episodes
    (inference.py:17-40).  The JSON output splits compile+warmup wall
    (``compile_warmup_s``: everything up to the first completed control
    step) from steady-state episode time (``steady_s``) — the cold-start
    cost the serving path (``cli serve``) exists to amortize is visible
    here, not hidden inside the total."""
    from .agents.trainer import Trainer
    from .utils.checkpoint import load_full_or_partial, read_checkpoint_meta

    import numpy as _np

    from .runtime import enable_compile_cache
    enable_compile_cache()
    if precision is None:
        precision = read_checkpoint_meta(checkpoint).get("precision")
    env, driver, agent = _build(agent_config, simulator_config, service,
                                scheduler, seed, max_nodes, max_edges,
                                resource_functions_path,
                                precision=precision)
    trainer = Trainer(env, driver, agent, seed=seed)
    topo, traffic = driver.episode(0, test_mode=True)
    _, obs = env.reset(jax.random.PRNGKey(seed), topo, traffic)
    example = trainer.ddpg.init(jax.random.PRNGKey(0), obs)
    example_buffer = trainer.ddpg.init_buffer(obs)
    # full train checkpoint (state + replay + episode counter), or a
    # state-only / legacy-replay-format checkpoint via partial restore
    state = load_full_or_partial(
        checkpoint, example, example_buffer=example_buffer,
        example_extra={"episode": _np.asarray(0, _np.int32)})[0]["state"]
    out = trainer.evaluate(state, episodes=episodes, test_mode=True)
    click.echo(json.dumps(out))


@cli.command()
@click.argument("agent_config")
@click.argument("simulator_config")
@click.argument("service")
@click.argument("scheduler")
@click.argument("checkpoint", required=False)
@click.option("--requests", default=64, show_default=True,
              help="synthetic coordination requests the built-in load "
                   "driver fires through the server (the programmatic "
                   "surface is PolicyServer.submit)")
@click.option("--concurrency", default=4, show_default=True,
              help="closed-loop client threads submitting concurrently — "
                   "what actually fills the larger batch buckets")
@click.option("--buckets", default="1,4,8", show_default=True,
              help="comma-separated batch-size buckets; each gets its own "
                   "AOT-compiled executable, a request batch runs in the "
                   "smallest bucket that fits it")
@click.option("--deadline-ms", default=5.0, show_default=True,
              help="max wait before a partially-filled batch flushes (the "
                   "latency a lone request pays for batching; with "
                   "--continuous it only bounds SLO deadline-miss "
                   "accounting — continuous batching never waits it out)")
@click.option("--continuous", is_flag=True, default=False,
              help="continuous batching: the next batch is formed while "
                   "the current device call is in flight and dispatches "
                   "the moment the device frees — requests join the next "
                   "dispatch instead of waiting out --deadline-ms.  "
                   "Latency-optimal at low rate (a lone request never "
                   "idles a deadline away), batch-optimal under load "
                   "(the in-flight backlog becomes the next batch).  "
                   "Default: the historic deadline batcher")
@click.option("--workers", default=1, show_default=True,
              help="serving fleet size: N PolicyServer replicas behind "
                   "least-queue-depth dispatch, every serve metric "
                   "tagged worker=w<i>.  A learned-tier fleet also gets "
                   "an SPR brownout tier that absorbs overflow (full "
                   "worker queue, or SLO budget burn past "
                   "--brownout-burn with a backlog) instead of "
                   "rejecting.  1 = the historic single server")
@click.option("--brownout-burn", default=2.0, show_default=True,
              help="error-budget burn rate above which a backlogged "
                   "fleet sheds new load to the SPR tier (needs "
                   "--workers > 1, a checkpoint and --slo-p99-ms; "
                   "0 disables proactive shedding — overflow shedding "
                   "on a full queue stays on)")
@click.option("--hot-swap-dir", default=None,
              help="live weight hot-swap: watch this publish directory "
                   "(serve.fleet.WeightPublisher layout — cli train "
                   "--hot-swap-dir writes it) and swap newly published "
                   "weight versions in BETWEEN device dispatches, zero "
                   "requests dropped, no batch ever mixing versions; "
                   "every serve_flush event/span carries the "
                   "policy_version that answered it")
@click.option("--swap-poll-s", default=0.2, show_default=True,
              help="seconds between hot-swap directory polls")
@click.option("--fire-swaps", default=0, show_default=True,
              help="self-test/bench hook: publish this many weight "
                   "versions into --hot-swap-dir WHILE the synthetic "
                   "load runs (spaced across the request count), so "
                   "hot-swap-under-fire is measurable from one command.  "
                   "The published payload is the serving tier's own "
                   "current weights (learned: the restored actor params; "
                   "SPR: the precomputed schedule action), so answers "
                   "stay bit-stable while the full swap path — publish, "
                   "watch, validate, lock, swap, stamp — executes under "
                   "load")
@click.option("--artifact-cache", default=None,
              help="compiled-policy artifact cache dir (serialized "
                   "jax.export modules keyed by checkpoint fingerprint + "
                   "shapes + precision + jaxlib).  Default: "
                   "<result-dir>/serve_cache — shared across runs, so a "
                   "warm restart skips policy tracing entirely")
@click.option("--pool-steps", default=8, show_default=True,
              help="env steps rolled (uniform schedule) to build the "
                   "synthetic request pool of distinct observations")
@click.option("--stats-interval", default=50, show_default=True,
              help="completed requests between serve_stats events")
@click.option("--request-timeout", default=120.0, show_default=True,
              help="seconds one driver client waits for its answer")
@click.option("--seed", default=0, show_default=True)
@click.option("--max-nodes", default=24, show_default=True)
@click.option("--max-edges", default=37, show_default=True)
@click.option("--resource-functions-path", default=None,
              help="dir (or .py file) of user resource-function plugins")
@click.option("--result-dir", default="results", show_default=True)
@click.option("--obs/--no-obs", "obs_enabled", default=True,
              show_default=True,
              help="serving telemetry through the run observer: "
                   "serve_start/serve_stats events + latency histograms "
                   "in events.jsonl/metrics.json (tools/obs_report.py "
                   "renders the serving section)")
@click.option("--obs-dir", default=None,
              help="directory for events.jsonl/metrics.json "
                   "(default: the run's result dir)")
@click.option("--obs-series-window", default=1024, show_default=True,
              help="flight recorder: points kept per metric in the hub's "
                   "time-series rings (the fleet dispatcher samples "
                   "queue depth, bucket occupancy, burn and pad waste "
                   "into them at the burn-refresh cadence; series.json "
                   "and /series read them back).  0 disables history")
@click.option("--perf/--no-perf", "perf_enabled", default=True,
              show_default=True,
              help="device-cost ledger over the serving buckets: each "
                   "serve_policy_b<B> records compiled FLOPs/bytes/"
                   "fusions at start() and its measured latency merges "
                   "in at close() — perf.json lands next to metrics.json")
@click.option("--metrics-port", default=0, show_default=True,
              help="live Prometheus /metrics endpoint over the serving "
                   "hub (the same endpoint cli train exposes): latency "
                   "histograms, queue depth and bucket occupancy are "
                   "scrapeable while the server runs.  0 = disabled; "
                   "requires --obs")
@click.option("--trace-sample", default=0, show_default=True,
              help="head-sample every Nth request into a "
                   "serve_request_span event (queue-wait / batch-wait / "
                   "device / fan-out split; the trace exporter renders "
                   "them flow-linked to their flush).  0 = request "
                   "spans off; flush-level serve_flush spans and the "
                   "latency-decomposition histograms are always "
                   "recorded under --obs.  Requires --obs")
@click.option("--slo-p99-ms", default=None,
              help="declarative latency objective(s) the SLO engine "
                   "judges rolling attainment + error-budget burn "
                   "against.  Grammar: '<ms>' overall, "
                   "'<bucket>:<ms>' per bucket, comma-separated — e.g. "
                   "'25' or '25,8:60'.  Off by default (deadline-miss "
                   "ratio, pad waste and arrival rate are tracked "
                   "regardless).  Requires --obs")
def serve(agent_config, simulator_config, service, scheduler, checkpoint,
          requests, concurrency, buckets, deadline_ms, continuous,
          workers, brownout_burn, hot_swap_dir, swap_poll_s, fire_swaps,
          artifact_cache, pool_steps, stats_interval, request_timeout,
          seed, max_nodes, max_edges, resource_functions_path, result_dir,
          obs_enabled, obs_dir, obs_series_window, perf_enabled,
          metrics_port, trace_sample, slo_p99_ms):
    """Serve coordination decisions from an AOT-compiled greedy policy.

    With CHECKPOINT: restores the actor, ahead-of-time compiles the
    batched greedy policy for every bucket (artifact-cache backed — a
    warm restart deserializes instead of re-tracing, so startup drops
    from minutes to seconds), then answers micro-batched requests.
    Without CHECKPOINT: the SPR shortest-path heuristic serves as the
    non-learned fallback tier through the same queue and accounting.

    Fleet mode (--workers N) runs N server replicas behind
    least-queue-depth dispatch with an SPR brownout tier;
    --hot-swap-dir makes every worker watch a weight-publish directory
    (written by a concurrent `cli train --hot-swap-dir` run) and swap
    new policy versions in between dispatches — train-while-serve with
    zero dropped requests across a swap.

    This command drives itself with a synthetic closed-loop request load
    (--requests/--concurrency over a pool of real observations) and
    reports requests/s + p50/p99 latency as JSON — the in-process SLA
    measurement loop that tools/serve_bench.py banks as SERVE_*.json."""
    import threading
    import time as _time

    import jax.numpy as jnp
    import numpy as _np

    from .agents.ddpg import DDPG
    from .serve import (ArtifactCache, FleetDispatcher, GreedyServePolicy,
                        PolicyServer, SPRFallbackPolicy)
    from .utils.experiment import setup_result_dir

    try:
        bucket_sizes = tuple(sorted({int(b) for b in buckets.split(",")}))
        if not bucket_sizes or any(b < 1 for b in bucket_sizes):
            raise ValueError
    except ValueError:
        raise click.BadParameter(
            f"--buckets must be comma-separated positive ints, got "
            f"{buckets!r}")
    if requests < 1 or concurrency < 1:
        raise click.BadParameter("--requests and --concurrency must be "
                                 "positive")
    if workers < 1:
        raise click.BadParameter("--workers must be >= 1")
    if fire_swaps < 0:
        raise click.BadParameter("--fire-swaps must be >= 0")
    if fire_swaps and not hot_swap_dir:
        raise click.BadParameter("--fire-swaps publishes into the hot-"
                                 "swap directory — pass --hot-swap-dir")
    if swap_poll_s <= 0:
        raise click.BadParameter("--swap-poll-s must be > 0")
    if metrics_port < 0:
        raise click.BadParameter("--metrics-port must be >= 0 "
                                 "(0 = disabled)")
    if metrics_port and not obs_enabled:
        raise click.BadParameter("--metrics-port needs the run observer "
                                 "(drop --no-obs)")
    if trace_sample < 0:
        raise click.BadParameter("--trace-sample must be >= 0 "
                                 "(0 = request spans off)")
    if (trace_sample or slo_p99_ms) and not obs_enabled:
        raise click.BadParameter("--trace-sample/--slo-p99-ms need the "
                                 "run observer (drop --no-obs)")
    slo_objectives = None
    if slo_p99_ms:
        from .obs import parse_slo_spec
        try:
            slo_objectives = parse_slo_spec(slo_p99_ms)
        except ValueError as e:
            raise click.BadParameter(f"--slo-p99-ms {slo_p99_ms!r}: {e}")
    from .runtime import device_summary, enable_compile_cache
    jax_cache_dir = enable_compile_cache()

    precision = None
    if checkpoint:
        from .utils.checkpoint import read_checkpoint_meta
        precision = read_checkpoint_meta(checkpoint).get("precision")
    env, driver, agent = _build(agent_config, simulator_config, service,
                                scheduler, seed, max_nodes, max_edges,
                                resource_functions_path,
                                precision=precision)
    ddpg = DDPG(env, agent)
    topo, traffic = driver.episode(0, test_mode=True)
    env_state, obs0 = env.reset(jax.random.PRNGKey(seed), topo, traffic)

    # request pool: distinct real observations from rolling the env under
    # the uniform dummy schedule (works with or without a checkpoint) —
    # collected BEFORE serving starts so pool construction never pollutes
    # the latency measurement
    to_host = lambda tree: jax.tree_util.tree_map(_np.asarray, tree)
    uniform_action = jnp.asarray(_uniform_schedule_action(
        env.limits, _np.asarray(topo.node_mask)))
    pool = [to_host(obs0)]
    ob = obs0
    for _ in range(max(pool_steps, 0)):
        env_state, ob, _, _, _ = env.step(env_state, topo, traffic,
                                          uniform_action)
        pool.append(to_host(ob))

    rdir = setup_result_dir(result_dir, "serve")
    cache_dir = artifact_cache or os.path.join(result_dir, "serve_cache")
    tier = "learned" if checkpoint else "spr"
    obs_rec = None
    if obs_enabled:
        from .obs import RunObserver
        obs_rec = RunObserver(obs_dir or rdir, tags={"seed": seed},
                              perf=perf_enabled,
                              metrics_port=(metrics_port or None),
                              series_window=obs_series_window)
        obs_rec.start(meta={
            "mode": "serve", "tier": tier, "seed": seed,
            "requests": requests, "concurrency": concurrency,
            "buckets": list(bucket_sizes), "deadline_ms": deadline_ms,
            "batch_mode": "continuous" if continuous else "deadline",
            "workers": workers, "hot_swap_dir": hot_swap_dir,
            "fire_swaps": fire_swaps,
            "trace_sample": trace_sample, "slo_p99_ms": slo_p99_ms,
            "precision": agent.precision,
            "unroll": env.sim_cfg.scan_unroll,
            "jax_cache_dir": jax_cache_dir,
            "checkpoint": checkpoint, "result_dir": rdir})
    # the latency/queue series live in the hub, and the command's JSON
    # output is read off them — so --no-obs (no events.jsonl/metrics.json)
    # still gets a private, sink-less hub; otherwise p50/p99 would print
    # as a fake-perfect 0.0 instead of a measurement
    if obs_rec is not None:
        hub = obs_rec.hub
    else:
        from .obs import MetricsHub
        hub = MetricsHub(tags={"seed": seed})
    # request-path tracing + SLO engine ride the observer: flush spans
    # and decomposition always recorded under --obs, request spans
    # head-sampled by --trace-sample, slo.json written at close.  With
    # --no-obs the server runs the historic tracer-free path.  Fleet
    # workers each get their OWN tracer (a tracer binds one SLO engine);
    # they share the hub, so the histograms/events merge fleet-wide.
    slo_path = obs_rec.slo_path if obs_rec is not None else None

    def make_tracer():
        if obs_rec is None:
            return None
        from .obs import ServeTracer
        return ServeTracer(hub=hub, sample=trace_sample)

    mode = "continuous" if continuous else "deadline"
    common = dict(buckets=bucket_sizes, deadline_ms=deadline_ms, hub=hub,
                  stats_interval=stats_interval, mode=mode,
                  hot_swap_dir=hot_swap_dir, swap_poll_s=swap_poll_s,
                  slo=slo_objectives)
    try:
        spr_fallback = lambda: SPRFallbackPolicy(topo, env.limits, obs0)
        swap_payload = None   # what --fire-swaps publishes
        if checkpoint:
            from .utils.checkpoint import (checkpoint_fingerprint,
                                           load_full_or_partial)
            example = ddpg.init(jax.random.PRNGKey(0), obs0)
            example_buffer = ddpg.init_buffer(obs0)
            state = load_full_or_partial(
                checkpoint, example, example_buffer=example_buffer,
                example_extra={"episode": _np.asarray(0, _np.int32)}
            )[0]["state"]
            learned = dict(
                policy=GreedyServePolicy(ddpg, obs0),
                params=state.actor_params,
                cache=ArtifactCache(cache_dir),
                fingerprint=checkpoint_fingerprint(checkpoint),
                precision=agent.precision,
                graph_mode=agent.graph_mode)
            swap_payload = jax.device_get(state.actor_params)
            if workers == 1:
                frontend = server = PolicyServer(
                    **common, **learned,
                    perf=(obs_rec.perf if obs_rec is not None else None),
                    tracer=make_tracer(), slo_path=slo_path)
            else:
                # the cost ledger rides worker 0 only: the per-bucket
                # compile capture is identical across workers, and the
                # serve_batch_ms histogram it merges at close is the
                # fleet aggregate already
                fleet = [PolicyServer(
                    **common, **learned, worker=f"w{i}",
                    perf=(obs_rec.perf if obs_rec is not None and i == 0
                          else None),
                    tracer=make_tracer()) for i in range(workers)]
                brownout = PolicyServer(
                    fallback=spr_fallback(), buckets=bucket_sizes,
                    deadline_ms=deadline_ms, hub=hub, worker="spr",
                    mode=mode, stats_interval=stats_interval,
                    tracer=make_tracer(), slo=slo_objectives)
                frontend = FleetDispatcher(
                    fleet, spr=brownout, hub=hub,
                    brownout_burn=(brownout_burn or None))
                server = fleet[0]
        else:
            if workers == 1:
                frontend = server = PolicyServer(
                    **common, fallback=spr_fallback(),
                    tracer=make_tracer(), slo_path=slo_path)
            else:
                # an SPR fleet IS the bottom tier — no brownout target
                # below it; overflow rejects like the single server would
                fleet = [PolicyServer(
                    **common, fallback=spr_fallback(), worker=f"w{i}",
                    tracer=make_tracer()) for i in range(workers)]
                frontend = FleetDispatcher(fleet, hub=hub,
                                           brownout_burn=None)
                server = fleet[0]
            if hot_swap_dir:
                # the SPR tier's "weights" are its precomputed schedule
                # action — what a fired swap republishes
                swap_payload = [_np.asarray(server.fallback.action)]
        frontend.start()

        # --fire-swaps: publish K versions of the CURRENT weights while
        # the load runs, spaced across the request count — the workers'
        # VersionWatchers must pick every one up under fire with zero
        # dropped requests (tools/fleet_smoke.py and serve_bench's
        # SERVE_r02 swap leg assert exactly that)
        fire_stop = threading.Event()
        fire_thread = None
        publisher = None
        if fire_swaps:
            from .serve.fleet import WeightPublisher
            publisher = WeightPublisher(hot_swap_dir, hub=hub)
            targets = [max(1, int(requests * (i + 1) / (fire_swaps + 1)))
                       for i in range(fire_swaps)]
            if workers > 1:
                adopted = lambda: min(w.policy_version for w in fleet)
            else:
                adopted = lambda: server.policy_version

            def _fire():
                # each publish waits for the PREVIOUS version to be
                # adopted by every worker: the watcher (correctly)
                # swaps straight to the newest version, so back-to-back
                # publishes within one poll interval would coalesce
                # into a single swap and undercount the exercised path
                fired = 0
                while fired < len(targets) and not fire_stop.is_set():
                    done = hub.get_counter("serve_requests_total")
                    if done >= targets[fired] \
                            and adopted() >= publisher.version:
                        publisher.publish(swap_payload,
                                          meta={"fired_at": int(done)})
                        fired += 1
                    else:
                        fire_stop.wait(0.003)

            fire_thread = threading.Thread(target=_fire, daemon=True,
                                           name="gsc-swap-firer")
            fire_thread.start()

        # closed-loop load: each client thread submits its share
        # sequentially, so at most --concurrency requests are in flight.
        # A failed request is collected so the JSON can name it — and
        # then fails the command (non-zero exit below)
        errors = []
        shares = [requests // concurrency + (1 if i < requests % concurrency
                                             else 0)
                  for i in range(concurrency)]

        def client(tid: int, n: int):
            for j in range(n):
                ob_h = pool[(tid + j * concurrency) % len(pool)]
                try:
                    frontend.submit(ob_h).result(request_timeout)
                except Exception as e:  # noqa: BLE001 - fails the command
                    errors.append(f"client{tid}/{j}: "
                                  f"{type(e).__name__}: {e}")

        t0 = _time.perf_counter()
        threads = [threading.Thread(target=client, args=(i, n),
                                    name=f"gsc-serve-client-{i}",
                                    daemon=True)
                   for i, n in enumerate(shares) if n]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = _time.perf_counter() - t0
        if fire_thread is not None:
            # let the firer finish its remaining publishes (adoption-
            # gated, so this is at most a few poll periods) before the
            # backstop stop
            fire_thread.join(timeout=10.0)
            fire_stop.set()
            fire_thread.join(timeout=5.0)
            # bounded wait for the watchers to adopt the last published
            # version, so the JSON's swap count is deterministic (the
            # load is done; this costs at most a few poll periods)
            swap_total = (frontend.swap_total if workers > 1
                          else lambda: server.swaps)
            want = publisher.version * (workers if workers > 1 else 1)
            deadline_wait = _time.perf_counter() + 5.0
            while swap_total() < want \
                    and _time.perf_counter() < deadline_wait:
                _time.sleep(swap_poll_s / 4)
        lat = server.latency_summary() or {}
        per_bucket = {}
        for b in bucket_sizes:
            s = server.latency_summary(b)
            if s and s.get("count"):
                per_bucket[str(b)] = {
                    "requests": int(s["count"]),
                    "p50_ms": round(s["p50"], 3),
                    "p99_ms": round(s["p99"], 3)}
        swaps = frontend.swap_total() if workers > 1 else server.swaps
        brownout_counts = None
        if workers > 1:
            brownout_counts = {
                reason: int(hub.get_counter("serve_brownout_total",
                                            reason=reason))
                for reason in ("slo_burn", "overflow")}
        frontend.close()
        # AFTER close: the tracer's final synchronous drain runs inside
        # close(), so the engine has seen every flush — reading earlier
        # under-reports fast runs (the drainer thread ticks at 50 ms)
        slo_block = (frontend.slo_summary() if workers > 1
                     else server.slo_summary())
        if workers > 1 and slo_path is not None \
                and frontend.merged_slo() is not None:
            # the fleet's slo.json: merged engine snapshots + fleet-wide
            # latency percentiles (same schema bench_diff's slo rows
            # ingest; per-worker numbers ride under per_worker)
            from .obs.slo import SLO_SCHEMA_VERSION, write_slo_json
            merged = frontend.merged_slo()
            write_slo_json(slo_path, {
                "schema_version": SLO_SCHEMA_VERSION,
                "ts": round(_time.time(), 3),
                "run": hub.base_tags.get("run"),
                "tier": server.tier,
                "buckets": list(bucket_sizes),
                "requests_completed": frontend.completed,
                "p50_latency_ms": round(lat.get("p50", 0.0), 4),
                "p99_latency_ms": round(lat.get("p99", 0.0), 4),
                **merged})
    except BaseException:
        if obs_rec is not None:
            try:
                obs_rec.close(status="error")
            except Exception:
                pass
        raise
    if obs_rec is not None:
        obs_rec.close(status="ok")
    completed = requests - len(errors)
    click.echo(json.dumps({
        "tier": server.tier, "requests": requests, "completed": completed,
        "workers": workers, "mode": mode,
        "errors": len(errors), "error_detail": errors[:5],
        "device": device_summary(),
        "wall_s": round(wall, 3),
        # completed requests only: a failed request is not throughput
        "rps": round(completed / wall, 3) if wall > 0 else 0.0,
        "p50_ms": round(lat.get("p50", 0.0), 3),
        "p99_ms": round(lat.get("p99", 0.0), 3),
        "buckets": per_bucket,
        "slo": slo_block,
        "swaps": swaps,
        "published_versions": (publisher.version if publisher else 0),
        "policy_version": server.policy_version,
        "brownout": brownout_counts,
        "startup": server.startup,
        "artifact_cache": cache_dir if checkpoint else None,
        "jax_cache_dir": jax_cache_dir,
        "result_dir": rdir}))
    if errors:
        raise click.ClickException(
            f"{len(errors)} of {requests} requests failed (first: "
            f"{errors[0]})")


@cli.command()
@click.option("--duration", "-d", default=1000.0, show_default=True,
              help="simulated ms")
@click.option("--network", "-n", required=True)
@click.option("--service", "-sf", required=True)
@click.option("--config", "-c", required=True)
@click.option("--seed", default=0, show_default=True)
@click.option("--max-nodes", default=24, show_default=True)
@click.option("--max-edges", default=37, show_default=True)
@click.option("--resource-functions-path", default=None,
              help="dir (or .py file) of user resource-function plugins")
@click.option("--per-flow-algo", type=click.Choice(["local", "spr"]),
              default="local", show_default=True,
              help="per-flow decision algorithm when the simulator config "
              "sets controller: per_flow — 'local' processes every flow at "
              "its current node (jitted policy); 'spr' runs the "
              "shortest-path heuristic through the host-side "
              "PerFlowController (the reference's FlowController loop)")
def simulate(duration, network, service, config, seed, max_nodes, max_edges,
             resource_functions_path, per_flow_algo):
    """Standalone simulator run with a uniform schedule over all nodes and
    every SF placed everywhere — the smoke-run mode of coordsim/main.py:19-89
    (which uses hard-coded dummy placement/schedule tables)."""
    import jax.numpy as jnp

    from .config.loader import load_service, load_sim
    from .config.schema import DROP_REASONS, EnvLimits
    from .sim.engine import SimEngine
    from .sim.traffic import generate_traffic
    from .topology.compiler import check_dt_quantization, load_topology

    svc = load_service(service,
                       resource_functions_path=resource_functions_path)
    sim_cfg = load_sim(config)
    if per_flow_algo != "local" and sim_cfg.controller != "per_flow":
        # fail BEFORE the expensive setup (GraphML load, traffic
        # generation, engine init) — the mismatch is knowable right here
        raise click.BadParameter(
            f"--per-flow-algo {per_flow_algo} requires 'controller: "
            "per_flow' in the simulator config (this config runs the "
            "duration controller, which would silently ignore the "
            "algorithm)")
    limits = EnvLimits.for_service(svc, max_nodes=max_nodes,
                                   max_edges=max_edges)
    topo = load_topology(network, max_nodes=max_nodes, max_edges=max_edges,
                         force_link_cap=sim_cfg.force_link_cap,
                         force_node_cap=sim_cfg.force_node_cap, seed=seed)
    check_dt_quantization(topo, sim_cfg.dt, name=network)
    steps = int(np.ceil(duration / sim_cfg.run_duration))
    if steps < 1:
        raise click.BadParameter("duration must cover at least one "
                                 f"run_duration ({sim_cfg.run_duration} ms)")
    traffic = generate_traffic(sim_cfg, svc, topo, steps, seed)
    engine = SimEngine(svc, sim_cfg, limits)

    nm = np.asarray(topo.node_mask)
    state = engine.init(jax.random.PRNGKey(seed), topo)
    if sim_cfg.controller == "per_flow":
        # FlowController granularity (flow_controller.py:21-92): each
        # deciding flow gets an individual destination every substep.
        if per_flow_algo == "spr":
            # host-side external algorithm through PerFlowController —
            # the loop a reference user writes against
            # FlowController.get_init_state/get_next_state
            from .sim.perflow import PerFlowController
            from .sim.spr import run_spr_episode

            ctrl = PerFlowController(engine, topo, traffic)
            state = run_spr_episode(ctrl, state, steps * engine.substeps)
            metrics = state.metrics
        else:
            # jitted local policy: process at the flow's node
            # (place-on-decision installs the SF; idle instances are
            # GC'd after vnf_timeout)
            from .sim.state import PH_DECIDE

            def decide_local(st):
                deciding = st.flows.phase == PH_DECIDE
                return jnp.where(deciding, st.flows.node, -1)

            for _ in range(steps):
                state, metrics = engine.apply_per_flow(state, topo, traffic,
                                                       decide_local)
    else:
        sched = _uniform_schedule_action(limits, nm).reshape(
            limits.scheduling_shape)
        placement = jnp.asarray(np.broadcast_to(nm[:, None],
                                                (max_nodes, limits.sf_pool)))
        for _ in range(steps):
            state, metrics = engine.apply(state, topo, traffic,
                                          jnp.asarray(sched), placement)
    m = metrics
    click.echo(json.dumps({
        "total_flows": int(m.generated), "successful_flows": int(m.processed),
        "dropped_flows": int(m.dropped),
        "drop_reasons": {k: int(v) for k, v in
                         zip(DROP_REASONS, np.asarray(m.drop_reasons))},
        "avg_end2end_delay": float(m.avg_e2e()),
    }))


if __name__ == "__main__":
    cli()
