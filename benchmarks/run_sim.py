"""Online serving benchmark: sweep dispatch policy x admission control x
autoscaling across simulator scenarios and report per-configuration
latency / deadline / goodput metrics — the paper's comparisons, now under
sustained load with a closed-loop gateway.

Run:
  PYTHONPATH=src python benchmarks/run_sim.py \
      --scenario steady --policies uniform,proportional
  PYTHONPATH=src python benchmarks/run_sim.py --scenario overload
  PYTHONPATH=src python benchmarks/run_sim.py --scenario all --verbose \
      --json sim_metrics.json
  # continuous-batching A/B in the memory-bound short-seq regime
  PYTHONPATH=src python benchmarks/run_sim.py --scenario overload \
      --max-batch 1,32 --seq-len 8 --batch-bench-json
  # replay a real serving log (CSV/JSONL)
  PYTHONPATH=src python benchmarks/run_sim.py \
      --scenario trace:serving_log.csv --max-batch 32

Output: one CSV-ish row per (scenario, policy, control) with p50/p99
latency, the deadline-violation rate *for admitted requests*, goodput
(admitted requests that met their deadline, per sim-second), shed rate,
degraded-admission count, scale-up count + latency, and mean accuracy.
``--control`` picks the gateway configurations to sweep:

  none       PR 1 behaviour — every request admitted, fixed node set
  admission  token-bucket + SLO-feasibility gate (reject/degrade)
  autoscale  standby-pool scaling only (every request admitted)
  full       admission + autoscaling

``--scenario fleet-64`` / ``fleet-256`` run the large-fleet
control-plane stressors over a ``synthetic_fleet`` table of the
matching size (short per-fleet default horizons; they are excluded from
``all`` because event counts scale with fleet size).

``--json`` additionally dumps every row (plus the admission outcome and
scaling-action detail, per-run wall-clock, simulator events/sec, and —
for tenant scenarios — the per-tenant breakdown) under a versioned
``{"schema_version": ..., "rows": [...]}`` envelope — CI uploads this
as the nightly bench artifact so the metric trajectory is diffable
across commits. ``--bench-json`` (bare,
or with an explicit path) also writes a compact ``BENCH_3.json``
(goodput, p99, shed rate per scenario x policy x control cell, plus a
``wall_clock`` section with per-scenario totals and events/sec), by
default at the repo root; the committed copy is the perf-trajectory
anchor future PRs diff against, so only the nightly's full sweep shape
(``--scenario all --horizon 15``) should refresh it — hence the
explicit opt-in rather than piggybacking on every ``--json``.

Continuous batching: ``--max-batch`` sweeps engine-batch caps (1 =
batching off, the pre-batching execution model — its CSV stays
byte-identical to the pre-batching tool); ``--seq-len`` picks the
serving item size (short items are the memory-bound regime where
batching pays) and ``--formation-window`` the partial-batch hold
window. ``--batch-bench-json`` writes the batching A/B trajectory
(``BENCH_5.json``: goodput/p99/shed/plan-error per cell plus on/off
goodput ratios). ``--scenario trace:<path>`` replays a CSV/JSONL
serving log instead of a synthetic arrival process.

Multi-tenant fairness: ``--scenario tenants`` expands to the tenant
scenarios (noisy-neighbor / tenant-skew / flash-crowd-tenant; they stay
out of ``all`` because their metrics only mean something next to the
per-tenant breakdown). ``--fairshare`` picks the gateway fairness
bundle — per-tenant admission token buckets (each tenant's
``rate_limit`` from the scenario's TenantSpecs) plus a deficit-round-
robin fair queue in front of the gate (weights from each spec's
``fair_share``):

  auto   on for tenant scenarios, off otherwise (the default)
  on     force the bundle (tenant scenarios only)
  off    tenant-blind gateway, byte-identical to the pre-tenancy path
  both   sweep off then on — the fairness A/B (adds a CSV column)

``--tenants`` prints the per-tenant breakdown (offered / admitted /
shed / admitted-violation rate / service ratio / p99) to stderr under
each row. ``--tenant-bench-json`` writes the fairness trajectory
(``BENCH_7.json``); its headline contract is that with the bundle on,
one abusive tenant cannot raise the victims' admitted-violation rate
above the anchored epsilon. ``--check-tenants`` gates a fresh
``--fairshare both`` sweep against that committed anchor (victims'
admitted-violation rate <= epsilon, Jain within 10% of the anchor's
fs-on value) and exits non-zero on regression.

  PYTHONPATH=src python benchmarks/run_sim.py --scenario tenants \
      --policies proportional --control full --fairshare both \
      --horizon 20 --tenants --tenant-bench-json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

try:
    import repro  # noqa: F401
except ModuleNotFoundError:     # run from a checkout without PYTHONPATH=src
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir, "src"))

from repro.configs import get_config
from repro.control import (AdmissionController, Autoscaler,
                           FairShareScheduler)
from repro.core.cluster import (STANDBY_NODES, SimBackend, cluster_nodes,
                                synthetic_fleet)
from repro.core.profiling import ProfilingTable
from repro.core.resource_manager import GatewayNode
from repro.core.variants import VariantPool
from repro.sched import registered_policies
from repro.sched.policy import REFERENCE_PREFIX
from repro.sim import (FLEET_HORIZONS, FLEET_SCENARIOS, FLEET_SIZES,
                       SCENARIOS, TENANT_SCENARIOS, OnlineSimulator,
                       ShardedSimulator, build_scenario)
from repro.sim.scenarios import TRACE_PREFIX

ARCH = "phi4-mini-3.8b"
CONTROL_MODES = ("none", "admission", "autoscale", "full")
FAIRSHARE_MODES = ("auto", "on", "off", "both")
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_COMPACT = os.path.join(REPO_ROOT, "BENCH_3.json")
BENCH_BATCH = os.path.join(REPO_ROOT, "BENCH_5.json")
BENCH_TENANT = os.path.join(REPO_ROOT, "BENCH_7.json")
# version stamp on every JSON artifact this tool writes (--json,
# --bench-json, --batch-bench-json, --tenant-bench-json) so downstream
# diffs/gates can tell a shape change from a metric change
SCHEMA_VERSION = 1
# fair-queue outstanding cap for the --fairshare bundle: one max-size
# request (item_choices tops out at 650) of in-flight work per tenant
# beyond its water-filled share. The DRR quantum alone orders release;
# the cap is what keeps a flooding tenant from parking the whole gate
# budget in its own queue between drains.
FAIR_OUTSTANDING_ITEMS = 650
# the classic sweep stays the paper's five policies so the committed
# BENCH_3.json cells and the nightly CSV keep their shape; new registry
# entries (accuracy_edf, ...) run when named via --policies
SWEEP_POLICIES = ("uniform", "uniform_apx", "asymmetric", "proportional",
                  "exact_oracle")
# the batching A/B runs in the short-sequence serving regime (the
# paper's small-item edge workload): per-item compute is tiny there, so
# weight streaming dominates and the engine batch is the lever. At the
# classic seq_len=512 prefill is compute-bound at every batch size and
# batching is (correctly) a no-op
BATCH_AB_SEQ_LEN = 8


def _fleet_profiles(scenario_name: str, num_standby: int, seed: int):
    """NodeProfile list for a scenario: a synthetic heterogeneous fleet
    of the matching size for fleet scenarios, else the paper's default
    4-board cluster (+ standby slices)."""
    if scenario_name in FLEET_SIZES:
        return synthetic_fleet(FLEET_SIZES[scenario_name], seed=seed,
                               num_standby=num_standby)
    return cluster_nodes(num_standby)


def _fresh_table(scenario_name: str, num_standby: int, seed: int,
                 seq_len: int = 512) -> ProfilingTable:
    """Each run gets its own table: the GN mutates it (straggler EWMA,
    availability, re-profiling), so sharing would leak state. Standby
    slices are present-but-unavailable in *every* mode so the seeded
    arrival trace is identical across control configurations. Fleet
    scenarios get a synthetic heterogeneous fleet of the matching size
    instead of the paper's default 4-board cluster."""
    pool = VariantPool(get_config(ARCH))
    nodes = _fleet_profiles(scenario_name, num_standby, seed)
    return ProfilingTable(pool, nodes, seq_len=seq_len)


def run_one(scenario_name: str, policy: str, control: str, *, seed: int,
            horizon_s: float, noise_std: float, num_standby: int,
            admission_rate: float, verbose: bool, max_batch: int = 1,
            seq_len: int = 512, formation_window_s: float = 0.0,
            cells: int = 0, cell_strategy: str = "stripe",
            router: str = "least-backlog",
            rebalance_s: float = 0.0, fair: bool = False,
            tenant_batch_cap: int = 0, profiler=None) -> dict:
    t_wall = time.perf_counter()
    table = _fresh_table(scenario_name, num_standby, seed, seq_len=seq_len)
    sc = build_scenario(scenario_name, table, seed=seed,
                        horizon_s=horizon_s)
    fs_weights = tenant_rates = None
    if fair:
        assert sc.tenants, (
            f"--fairshare needs a tenant scenario, got {scenario_name!r}")
        # the fairness bundle is declared by the scenario itself: DRR
        # weights from each tenant's fair_share entitlement, per-tenant
        # admission buckets from each tenant's rate_limit (the capacity
        # lever — DRR ordering alone cannot reallocate node backlog)
        fs_weights = {t.name: t.fair_share for t in sc.tenants}
        tenant_rates = {t.name: t.rate_limit for t in sc.tenants
                        if t.rate_limit is not None} or None
    if cells > 0:
        # sharded control plane: per-cell gateway stacks behind a root
        # router. cells=1 is byte-identical to the unsharded path below
        # (pinned by tests/test_shard.py), so the same trace compares.
        pool = VariantPool(get_config(ARCH))
        profiles = _fleet_profiles(scenario_name, num_standby, seed)
        sim = ShardedSimulator(
            lambda ps: ProfilingTable(pool, ps, seq_len=seq_len),
            profiles, sc.arrivals, sc.faults,
            cells=cells, strategy=cell_strategy, router=router,
            policy=policy, seed=seed, noise_std=noise_std,
            scenario=sc.name, horizon_s=sc.horizon_s,
            admission=control in ("admission", "full"),
            admission_rate=(admission_rate if admission_rate > 0
                            else None),
            admission_tenant_rates=(tenant_rates
                                    if control in ("admission", "full")
                                    else None),
            autoscale=(control in ("autoscale", "full")
                       and num_standby > 0),
            max_batch=max_batch,
            formation_window_s=formation_window_s,
            fairshare=fair, fairshare_weights=fs_weights,
            rebalance_s=rebalance_s)
    else:
        gn = GatewayNode(table, SimBackend(table, noise_std=noise_std,
                                           seed=seed), policy=policy,
                         max_batch=max_batch)
        admission = None
        if control in ("admission", "full"):
            admission = AdmissionController(
                table, rate=admission_rate if admission_rate > 0 else None,
                tenant_rates=tenant_rates)
        autoscaler = None
        if control in ("autoscale", "full") and num_standby > 0:
            standby_names = [n.name for n in table.nodes if not n.available]
            autoscaler = Autoscaler(table, standby_names)
        fairshare = None
        if fair:
            fairshare = FairShareScheduler(
                fs_weights, max_outstanding_items=FAIR_OUTSTANDING_ITEMS)
        sim = OnlineSimulator(gn, sc.arrivals, sc.faults,
                              scenario=sc.name, horizon_s=sc.horizon_s,
                              admission=admission, autoscaler=autoscaler,
                              fairshare=fairshare,
                              tenant_batch_cap=tenant_batch_cap,
                              formation_window_s=formation_window_s)
    # --profile: the event/root loop alone (sim.run), excluding table
    # builds and trace generation; one shared profiler accumulates
    # across every swept cell so a sweep profiles like a single run
    if profiler is not None:
        profiler.enable()
    report = sim.run()
    if profiler is not None:
        profiler.disable()
    summary = report.summary()
    fallbacks = summary.get("plan_fallbacks", 0.0)
    if fallbacks:
        # e.g. exact_oracle beyond max_enum_nodes silently planning with
        # the paper heuristic — never let that pollute gap numbers unseen
        print(f"    [{policy}/{control}] WARNING: {fallbacks:.0f} "
              "plan(s) used a fallback policy (see Plan.meta)",
              file=sys.stderr)
    if verbose:
        for line in report.log:
            if any(k in line for k in
                   ("disconnect", "re-DISTRIBUTE", "reconnect",
                    "straggler", "parked", "REJECTED", "DEGRADED",
                    "scale-up", "scale-down", "node_up")):
                print(f"    [{policy}/{control}] {line}", file=sys.stderr)
    row = {"scenario": sc.name, "policy": policy, "control": control,
           "seed": seed, "max_batch": max_batch, "seq_len": seq_len,
           "cells": cells, "fairshare": bool(fair)}
    if sc.tenants:
        # per-tenant breakdown + who the scenario marks abusive (the
        # stack never reads the flag; the fairness gate's victim set is
        # everyone else)
        row["tenants"] = report.tenant_summary()
        row["abusive_tenants"] = sorted(
            t.name for t in sc.tenants if t.abusive)
    if cells > 0:
        row["cell_strategy"] = cell_strategy
        row["router"] = router
        row["rebalances"] = len(sim.rebalances)
        row["plans_made"] = sim.plans_made()
    row.update({k: float(v) for k, v in summary.items()})
    row["admission_counts"] = dict(report.admission_counts)
    row["scaling_actions"] = [
        {"kind": a.kind, "node": a.node, "decided_s": a.decided_s,
         "ready_s": a.ready_s, "reason": a.reason}
        for a in report.scaling]
    # control-plane wall-clock: the whole cell (table build + trace +
    # sim) and the event loop alone
    row["wall_clock_s"] = time.perf_counter() - t_wall
    row["sim_wall_s"] = report.wall_s
    row["sim_events"] = report.n_events
    row["events_per_sec"] = report.n_events / max(report.wall_s, 1e-9)
    return row


def _fair_modes(scenario_name: str, mode: str):
    """Fairshare settings to sweep for one scenario: ``auto`` turns the
    bundle on exactly for scenarios that declare tenants, ``both`` is
    the off-then-on A/B (validated to run over tenant scenarios only)."""
    if mode == "off":
        return [False]
    if mode == "auto":
        return [True] if scenario_name in TENANT_SCENARIOS else [False]
    return [False, True] if mode == "both" else [True]


def _print_tenants(row):
    fs = "on" if row["fairshare"] else "off"
    for name in sorted(row["tenants"]):
        m = row["tenants"][name]
        tag = (" (abusive)" if name in row.get("abusive_tenants", ())
               else "")
        print(f"    [{row['policy']}/{row['control']}/fs-{fs}] "
              f"tenant={name}{tag} offered={m['offered']:.0f} "
              f"admitted={m['admitted']:.0f} shed={m['shed_rate']:.3f} "
              f"viol={m['admitted_violation_rate']:.3f} "
              f"sr={m['service_ratio']:.3f} "
              f"p99={m['p99_latency_s']:.4f}s "
              f"goodput={m['goodput_rps']:.2f}", file=sys.stderr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--scenario", default="steady",
                    help=f"one of {sorted(SCENARIOS)}, a fleet scenario "
                         f"({sorted(FLEET_SCENARIOS)}), a tenant "
                         f"scenario ({sorted(TENANT_SCENARIOS)}), "
                         "'tenants' (all tenant scenarios), or 'all' "
                         "(the classic grid; fleet and tenant scenarios "
                         "run only when named — fleet event counts "
                         "scale with fleet size, tenant metrics only "
                         "mean something with the per-tenant breakdown)")
    policy_names = registered_policies()
    ap.add_argument("--policies", default=",".join(SWEEP_POLICIES),
                    help="comma-separated subset of "
                         f"{sorted(policy_names)} (default: the classic "
                         "five-policy sweep — newer registry entries run "
                         "when named)")
    ap.add_argument("--max-batch", default="1",
                    help="comma-separated engine-batch caps to sweep "
                         "(default 1 = continuous batching off, the "
                         "pre-batching execution model; e.g. '1,32' is "
                         "the batching A/B)")
    ap.add_argument("--seq-len", type=int, default=512,
                    help="profiling-table sequence length (the serving "
                         "item size). Short items (<=32) are the "
                         "memory-bound regime where batching pays; the "
                         f"A/B artifact uses {BATCH_AB_SEQ_LEN}")
    ap.add_argument("--formation-window", type=float, default=0.0,
                    help="continuous-batching partial-batch hold window "
                         "in sim-seconds (0 = launch as soon as the "
                         "server frees)")
    ap.add_argument("--batch-bench-json", nargs="?", const=BENCH_BATCH,
                    default="",
                    help="write the compact batching A/B trajectory "
                         "(goodput/p99/shed/plan-error per cell x "
                         "max_batch, plus on/off goodput ratios; default "
                         "path: BENCH_5.json at the repo root)")
    ap.add_argument("--control", default="none,full",
                    help="comma-separated subset of "
                         f"{CONTROL_MODES} to sweep")
    ap.add_argument("--fairshare", default="auto",
                    choices=FAIRSHARE_MODES,
                    help="multi-tenant fairness bundle (per-tenant "
                         "admission buckets + DRR fair queue): auto = on "
                         "for tenant scenarios / off otherwise, both = "
                         "the off-then-on A/B sweep (tenant scenarios "
                         "only)")
    ap.add_argument("--tenants", action="store_true",
                    help="print the per-tenant breakdown (offered / "
                         "admitted / shed / admitted-violation rate / "
                         "service ratio / p99) to stderr under each row")
    ap.add_argument("--tenant-batch-cap", type=int, default=0,
                    help="max items one tenant may claim in a formed "
                         "engine batch before the work-conserving fill "
                         "(0 = tenant-blind formation; unsharded path "
                         "only)")
    ap.add_argument("--tenant-bench-json", nargs="?", const=BENCH_TENANT,
                    default="",
                    help="write the compact tenant-fairness trajectory "
                         "from a --fairshare both sweep (per-cell "
                         "goodput/p99/shed/Jain + victims' admitted-"
                         "violation rate and service ratio; default "
                         "path: BENCH_7.json at the repo root)")
    ap.add_argument("--check-tenants", nargs="?", const=BENCH_TENANT,
                    default="",
                    help="gate this sweep's fs-on cells against a "
                         "committed tenant-fairness anchor (victims' "
                         "admitted-violation rate <= the anchored "
                         "epsilon, Jain within 10%% of the anchor); "
                         "exits 1 on regression")
    ap.add_argument("--standby", type=int, default=2,
                    help="standby nodes available to the autoscaler "
                         f"(0..{len(STANDBY_NODES)})")
    ap.add_argument("--admission-rate", type=float, default=0.0,
                    help="token-bucket refill rate in req/s "
                         "(<=0 disables rate shaping; the SLO-feasibility "
                         "gate always runs)")
    ap.add_argument("--cells", type=int, default=0,
                    help="shard the control plane into this many cells "
                         "(ShardedSimulator); 0 = the unsharded single "
                         "gateway. cells=1 is byte-identical to 0 and "
                         "exists to validate the sharding layer")
    ap.add_argument("--cell-strategy", default="stripe",
                    choices=("stripe", "by-class"),
                    help="fleet partition strategy (repro.sched.shard)")
    ap.add_argument("--router", default="least-backlog",
                    choices=("least-backlog", "rendezvous"),
                    help="root request-routing policy across cells")
    ap.add_argument("--rebalance", type=float, default=0.0,
                    help="root rebalance period in sim-seconds: move one "
                         "pooled standby node from the calmest to the "
                         "hottest cell when their normalized backlogs "
                         "diverge (0 = off; multi-cell only)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--horizon", type=float, default=None,
                    help="arrival horizon in sim-seconds (default: 30, "
                         "or the per-fleet default for fleet scenarios "
                         f"— {FLEET_HORIZONS})")
    ap.add_argument("--noise", type=float, default=0.0,
                    help="execution-time noise std (SimBackend)")
    ap.add_argument("--json", default="",
                    help="also dump all rows (with admission/scaling "
                         "detail) to this JSON file")
    ap.add_argument("--bench-json", nargs="?", const=BENCH_COMPACT,
                    default="",
                    help="also write the compact goodput/p99/shed "
                         "perf-trajectory file (default path: "
                         "BENCH_3.json at the repo root). Opt-in so a "
                         "partial dev sweep cannot clobber the "
                         "committed anchor")
    ap.add_argument("--profile", nargs="?", const="run_sim.prof",
                    default="",
                    help="dump a cProfile of the event/root loop "
                         "(sim.run only — table builds and trace "
                         "generation excluded) to this file (default: "
                         "run_sim.prof) and print the top self-time "
                         "functions; with --cells this profiles the "
                         "sharded root merge loop")
    ap.add_argument("--verbose", action="store_true",
                    help="print fault/admission/scaling log lines to "
                         "stderr")
    args = ap.parse_args(argv)

    scenario_names = (sorted(SCENARIOS) if args.scenario == "all"
                      else sorted(TENANT_SCENARIOS)
                      if args.scenario == "tenants"
                      else [args.scenario])
    for s in scenario_names:
        if s.startswith(TRACE_PREFIX):
            trace_path = s[len(TRACE_PREFIX):]
            if not os.path.exists(trace_path):
                ap.error(f"trace file not found: {trace_path!r}")
        elif (s not in SCENARIOS and s not in FLEET_SCENARIOS
              and s not in TENANT_SCENARIOS):
            ap.error(f"unknown scenario {s!r}; have {sorted(SCENARIOS)}, "
                     f"{sorted(FLEET_SCENARIOS)}, "
                     f"{sorted(TENANT_SCENARIOS)}, "
                     f"'{TRACE_PREFIX}<path>' (serving-log replay), "
                     "'tenants', or 'all'")
    policies = [p.strip() for p in args.policies.split(",") if p.strip()]
    if not policies:
        ap.error("--policies must name at least one policy "
                 f"from {sorted(policy_names)}")
    for p in policies:
        # reference:<name> rows measure the retained pre-PR planners
        base = p[len(REFERENCE_PREFIX):] if p.startswith(REFERENCE_PREFIX) \
            else p
        if base not in policy_names:
            ap.error(f"unknown policy {p!r}; have {sorted(policy_names)} "
                     f"(optionally prefixed with {REFERENCE_PREFIX!r})")
    controls = [c.strip() for c in args.control.split(",") if c.strip()]
    if not controls:
        ap.error(f"--control must name at least one of {CONTROL_MODES}")
    for c in controls:
        if c not in CONTROL_MODES:
            ap.error(f"unknown control mode {c!r}; have {CONTROL_MODES}")
    if args.horizon is not None and args.horizon <= 0:
        ap.error("--horizon must be > 0 sim-seconds")
    if args.cells < 0:
        ap.error("--cells must be >= 0 (0 = unsharded)")
    if args.rebalance < 0:
        ap.error("--rebalance must be >= 0 sim-seconds (0 = off)")
    try:
        batches = [int(b) for b in args.max_batch.split(",") if b.strip()]
    except ValueError:
        batches = []
    if not batches or any(b < 1 for b in batches):
        ap.error("--max-batch must be a comma-separated list of ints >= 1")
    if args.seq_len < 1:
        ap.error("--seq-len must be >= 1")
    if args.formation_window < 0:
        ap.error("--formation-window must be >= 0")
    non_tenant = [s for s in scenario_names if s not in TENANT_SCENARIOS]
    if args.fairshare in ("on", "both") and non_tenant:
        ap.error(f"--fairshare {args.fairshare} needs tenant scenarios "
                 "(the bundle's weights and rate limits come from the "
                 f"scenario's TenantSpecs); {non_tenant} declare none. "
                 "Use --scenario tenants or a name from "
                 f"{sorted(TENANT_SCENARIOS)}")
    if args.tenant_bench_json and (non_tenant or args.fairshare != "both"):
        # the fairness artifact is an A/B: every cell needs its fs-off
        # twin or the containment story has no baseline
        ap.error("--tenant-bench-json needs --fairshare both over "
                 "tenant scenarios only (e.g. --scenario tenants "
                 "--fairshare both)")
    if args.check_tenants and (
            non_tenant or args.fairshare not in ("auto", "on", "both")):
        ap.error("--check-tenants gates fs-on cells: run it over tenant "
                 "scenarios with --fairshare auto, on, or both")
    if args.tenant_batch_cap < 0:
        ap.error("--tenant-batch-cap must be >= 0 (0 = tenant-blind)")
    if args.tenant_batch_cap > 0 and args.cells > 0:
        ap.error("--tenant-batch-cap only plumbs into the unsharded "
                 "path (--cells 0); per-cell batch formation stays "
                 "tenant-blind")
    fleet_only = all(s in FLEET_SCENARIOS for s in scenario_names)
    if args.standby < 0:
        ap.error("--standby must be >= 0")
    if not fleet_only and args.standby > len(STANDBY_NODES):
        # classic cluster standby comes from the fixed STANDBY_NODES
        # pool; fleet tables synthesize any number of standby slices
        ap.error(f"--standby must be in 0..{len(STANDBY_NODES)} for "
                 "non-fleet scenarios")
    if args.standby == 0 and any(c in ("autoscale", "full")
                                 for c in controls):
        ap.error("--standby 0 leaves the autoscaler with an empty pool; "
                 "rows labeled 'autoscale'/'full' would silently behave "
                 "like 'none'/'admission' — raise --standby or drop "
                 "those control modes")

    cols = ("scenario", "policy", "control", "offered", "admitted",
            "completed", "shed_rate", "degraded", "p50_latency_s",
            "p99_latency_s", "deadline_violation_rate", "goodput_rps",
            "mean_acc", "scale_ups", "mean_scale_up_latency_s",
            "redistributes")
    # a bare batch-1 sweep keeps the exact pre-batching CSV shape (the
    # nightly diff anchor); a --max-batch sweep appends the batch column
    batch_sweep = batches != [1]
    if batch_sweep:
        cols = cols + ("max_batch",)
    # ... and a sweep that ever runs the fairness bundle appends the
    # fairshare column; pure fs-off sweeps keep the classic shape
    fair_sweep = any(True in _fair_modes(s, args.fairshare)
                     for s in scenario_names)
    if fair_sweep:
        cols = cols + ("fairshare",)
    print(",".join(cols))
    profiler = None
    if args.profile:
        import cProfile
        profiler = cProfile.Profile()
    rows = []
    for sname in scenario_names:
        horizon = args.horizon
        if horizon is None:
            # trace replay derives its horizon from the last logged
            # arrival unless one is forced explicitly
            horizon = (0.0 if sname.startswith(TRACE_PREFIX)
                       else FLEET_HORIZONS.get(sname, 30.0))
        for policy in policies:
            for control in controls:
                for max_batch, fair in (
                        (b, f) for b in batches
                        for f in _fair_modes(sname, args.fairshare)):
                    row = run_one(sname, policy, control, seed=args.seed,
                                  horizon_s=horizon,
                                  noise_std=args.noise,
                                  num_standby=args.standby,
                                  admission_rate=args.admission_rate,
                                  verbose=args.verbose,
                                  max_batch=max_batch,
                                  seq_len=args.seq_len,
                                  formation_window_s=args.formation_window,
                                  cells=args.cells,
                                  cell_strategy=args.cell_strategy,
                                  router=args.router,
                                  rebalance_s=args.rebalance,
                                  fair=fair,
                                  tenant_batch_cap=args.tenant_batch_cap,
                                  profiler=profiler)
                    rows.append(row)
                    out = [
                        row["scenario"], row["policy"], row["control"],
                        f"{row['offered']:.0f}", f"{row['admitted']:.0f}",
                        f"{row['completed']:.0f}",
                        f"{row['shed_rate']:.3f}",
                        f"{row['degraded']:.0f}",
                        f"{row['p50_latency_s']:.4f}",
                        f"{row['p99_latency_s']:.4f}",
                        f"{row['deadline_violation_rate']:.3f}",
                        f"{row['goodput_rps']:.2f}",
                        f"{row['mean_acc']:.2f}",
                        f"{row['scale_ups']:.0f}",
                        f"{row['mean_scale_up_latency_s']:.2f}",
                        f"{row['redistributes']:.0f}",
                    ]
                    if batch_sweep:
                        out.append(f"{row['max_batch']:d}")
                    if fair_sweep:
                        out.append("on" if row["fairshare"] else "off")
                    print(",".join(out))
                    if args.tenants and "tenants" in row:
                        _print_tenants(row)
    if profiler is not None:
        import pstats

        import profile_rollup
        profiler.dump_stats(args.profile)
        rollup = profile_rollup.module_rollup(profiler)
        print(f"profile: {profile_rollup.format_rollup(rollup)} across "
              f"{len(rows)} run(s) -> {args.profile} "
              "(inspect: python -m pstats)", file=sys.stderr)
        st = pstats.Stats(profiler)
        entries = sorted(
            ((tt, ct, f"{os.path.basename(fn)}:{name}")
             for (fn, _line, name), (_cc, _nc, tt, ct, _callers)
             in st.stats.items()), reverse=True)
        for tt, ct, name in entries[:10]:
            print(f"  {tt:8.3f}s self  {ct:8.3f}s cum  {name}",
                  file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"schema_version": SCHEMA_VERSION, "rows": rows},
                      f, indent=2, sort_keys=True)
        print(f"wrote {len(rows)} rows to {args.json}", file=sys.stderr)
    if args.bench_json:
        if batch_sweep:
            ap.error("--bench-json is the batching-off perf anchor "
                     "(BENCH_3); a --max-batch sweep writes the A/B "
                     "artifact via --batch-bench-json instead")
        write_bench_compact(rows, args, path=args.bench_json)
    if args.batch_bench_json:
        if not batch_sweep or 1 not in batches:
            # never let a partial run clobber the committed A/B anchor
            # with cells that cannot carry an on/off ratio
            ap.error("--batch-bench-json needs a --max-batch sweep that "
                     "includes 1 and a cap above it (e.g. "
                     "--max-batch 1,32), or the A/B ratios would be "
                     "empty")
        write_batch_bench(rows, args, batches, path=args.batch_bench_json)
    if args.tenant_bench_json:
        write_tenant_bench(rows, args, path=args.tenant_bench_json)
    if args.check_tenants:
        failures = check_tenant_fairness(rows, args.check_tenants)
        if failures:
            for msg in failures:
                print(f"FAIL {msg}", file=sys.stderr)
            return 1
        print(f"tenant fairness gate OK against {args.check_tenants}",
              file=sys.stderr)
    return 0


def _tenant_cell(row) -> dict:
    """One fairness cell: whole-run serving metrics plus the victims'
    (non-abusive tenants') worst-case view — the numbers the fairness
    contract is written against. Jain is over per-tenant service ratios,
    so it *drops* when containment works (the abuser's ratio collapses
    to its slice); that is why the gate compares fs-on against the
    anchored fs-on value rather than against the fs-off twin."""
    victims = [t for t in row["tenants"]
               if t not in row.get("abusive_tenants", ())]
    assert victims, "tenant scenario with no non-abusive tenant"
    cell = {
        "goodput_rps": round(row["goodput_rps"], 3),
        "p99_latency_s": round(row["p99_latency_s"], 5),
        "shed_rate": round(row["shed_rate"], 4),
        "jain": round(row["fairness_jain"], 4),
        "victim_violation_rate": round(
            max(row["tenants"][t]["admitted_violation_rate"]
                for t in victims), 4),
        "victim_service_ratio": round(
            min(row["tenants"][t]["service_ratio"] for t in victims), 4),
    }
    abusers = row.get("abusive_tenants") or []
    if abusers:
        cell["abuser_service_ratio"] = round(
            max(row["tenants"][t]["service_ratio"] for t in abusers), 4)
    return cell


def write_tenant_bench(rows, args, path: str = BENCH_TENANT):
    """Compact tenant-fairness artifact (``BENCH_7.json``): one cell per
    scenario x policy x control x fairshare from a ``--fairshare both``
    sweep. Every fs-on cell carries its ``epsilon`` — the ceiling on the
    victims' admitted-violation rate that ``--check-tenants`` (and the
    nightly) enforce; the committed copy anchors it at the measured
    value plus a small margin, so the headline contract is literal: one
    abusive tenant cannot push the victims' admitted-violation rate
    above epsilon while the fairness bundle is on."""
    cells = {}
    for r in rows:
        if "tenants" not in r:
            continue
        fs = "fs-on" if r["fairshare"] else "fs-off"
        cell = _tenant_cell(r)
        if r["fairshare"]:
            cell["epsilon"] = max(
                0.02, round(cell["victim_violation_rate"] + 0.01, 4))
        cells[f"{r['scenario']}/{r['policy']}/{r['control']}/{fs}"] = cell
    out = {
        "bench": "run_sim_tenant_fairness",
        "schema_version": SCHEMA_VERSION,
        "arch": ARCH,
        "seed": args.seed,
        "horizon_s": args.horizon,
        "fair_outstanding_items": FAIR_OUTSTANDING_ITEMS,
        "headline": "with the fairshare bundle on, an abusive tenant "
                    "cannot raise the victims' admitted-violation rate "
                    "above the cell's epsilon",
        "cells": cells,
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(cells)} tenant-fairness cells to {path}",
          file=sys.stderr)


def check_tenant_fairness(rows, anchor_path: str,
                          jain_tolerance: float = 0.10) -> list:
    """Gate a fresh sweep's fs-on cells against a committed
    ``BENCH_7.json``: victims' admitted-violation rate must stay within
    the anchored epsilon and Jain within ``jain_tolerance`` of the
    anchored fs-on value. Returns failure messages (empty = pass);
    anchor cells the sweep did not reproduce are skipped, but zero
    overlap is itself a failure (a mis-scoped sweep must not pass)."""
    try:
        with open(anchor_path) as f:
            anchor = json.load(f)
    except (OSError, ValueError) as e:
        return [f"cannot read tenant-fairness anchor {anchor_path}: {e}"]
    got_version = anchor.get("schema_version")
    if got_version != SCHEMA_VERSION:
        # comparing cells across schema generations produces nonsense
        # gates; the fix is to re-anchor, not to squint
        return [
            f"anchor {os.path.basename(anchor_path)} has schema_version "
            f"{got_version!r}, this tool writes {SCHEMA_VERSION} — "
            f"re-anchor needed: regenerate with `python benchmarks/"
            f"run_sim.py --scenario tenants --policies proportional "
            f"--control full --fairshare both --horizon 20 "
            f"--tenant-bench-json {os.path.basename(anchor_path)}` on a "
            "known-good tree and commit it"]
    fresh = {
        (f"{r['scenario']}/{r['policy']}/{r['control']}/fs-on"):
            _tenant_cell(r)
        for r in rows if "tenants" in r and r["fairshare"]}
    failures, checked = [], 0
    for key, cell in sorted(anchor.get("cells", {}).items()):
        if not key.endswith("/fs-on") or key not in fresh:
            continue
        checked += 1
        got = fresh[key]
        eps = cell.get("epsilon", 0.02)
        if got["victim_violation_rate"] > eps + 1e-9:
            failures.append(
                f"{key}: victims' admitted-violation rate "
                f"{got['victim_violation_rate']:.4f} > epsilon {eps}")
        floor = (1.0 - jain_tolerance) * cell["jain"]
        if got["jain"] < floor - 1e-9:
            failures.append(
                f"{key}: Jain {got['jain']:.4f} < floor {floor:.4f} "
                f"(anchor {cell['jain']:.4f} - {jain_tolerance:.0%})")
    if not checked:
        failures.append(
            f"no fs-on cells overlap between this sweep and "
            f"{anchor_path} — gate checked nothing")
    return failures


def write_batch_bench(rows, args, batches, path: str = BENCH_BATCH):
    """Compact batching A/B artifact (``BENCH_5.json``): one
    goodput/p99/shed/plan-error cell per scenario x policy x control x
    max_batch, plus an ``ab`` section with the batching-on/off goodput
    ratio per cell (on = the largest swept cap, off = max_batch 1). The
    committed copy is refreshed by the nightly ``--max-batch 1,32
    --seq-len 8`` overload sweep."""
    cells = {
        (f"{r['scenario']}/{r['policy']}/{r['control']}"
         f"/b{r['max_batch']}"): {
            "goodput_rps": round(r["goodput_rps"], 3),
            "p99_latency_s": round(r["p99_latency_s"], 5),
            "shed_rate": round(r["shed_rate"], 4),
            "plan_makespan_err": round(r["plan_makespan_err"], 5),
        }
        for r in rows}
    on = max(batches)
    ab = {}
    if on > 1 and 1 in batches:
        base = {(r["scenario"], r["policy"], r["control"]): r
                for r in rows if r["max_batch"] == 1}
        for r in rows:
            if r["max_batch"] != on:
                continue
            off = base.get((r["scenario"], r["policy"], r["control"]))
            if off is None or off["goodput_rps"] <= 0:
                continue
            key = f"{r['scenario']}/{r['policy']}/{r['control']}"
            ab[key] = round(r["goodput_rps"] / off["goodput_rps"], 3)
    out = {
        "bench": "run_sim_batching_ab",
        "schema_version": SCHEMA_VERSION,
        "arch": ARCH,
        "seed": args.seed,
        "seq_len": args.seq_len,
        "horizon_s": args.horizon,
        "max_batch_sweep": batches,
        "formation_window_s": args.formation_window,
        "cells": cells,
        "goodput_ratio_on_vs_off": ab,
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(cells)} batching cells to {path}", file=sys.stderr)


def write_bench_compact(rows, args, path: str = BENCH_COMPACT):
    """Compact perf-trajectory artifact: one goodput/p99/shed triple per
    scenario x policy x control cell, plus control-plane wall-clock
    aggregates (per scenario and total — the serving-metric cells stay
    machine-independent, the wall_clock section is the host-speed
    trajectory). The committed BENCH_3.json is this file for the nightly
    sweep's shape (--scenario all --horizon 15 --bench-json); CI uploads
    the fresh copy so regressions are a two-line diff."""
    cells = {
        f"{r['scenario']}/{r['policy']}/{r['control']}": {
            "goodput_rps": round(r["goodput_rps"], 3),
            "p99_latency_s": round(r["p99_latency_s"], 5),
            "shed_rate": round(r["shed_rate"], 4),
        }
        for r in rows}
    per_scenario: dict = {}
    for r in rows:
        per_scenario[r["scenario"]] = round(
            per_scenario.get(r["scenario"], 0.0) + r["wall_clock_s"], 3)
    total_events = sum(r["sim_events"] for r in rows)
    total_sim_wall = sum(r["sim_wall_s"] for r in rows)
    out = {
        "bench": "run_sim",
        "schema_version": SCHEMA_VERSION,
        "arch": ARCH,
        "seed": args.seed,
        "horizon_s": args.horizon if args.horizon is not None else 30.0,
        "standby": args.standby,
        "noise_std": args.noise,
        "cells": cells,
        "wall_clock": {
            "per_scenario_s": per_scenario,
            "total_s": round(sum(r["wall_clock_s"] for r in rows), 3),
            "events": int(total_events),
            "events_per_sec": round(
                total_events / max(total_sim_wall, 1e-9), 1),
        },
    }
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"wrote {len(cells)} compact cells to {path}", file=sys.stderr)


if __name__ == "__main__":
    sys.exit(main())
