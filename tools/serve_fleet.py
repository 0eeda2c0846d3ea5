#!/usr/bin/env python
"""Fleet router CLI: one endpoint over N serving replicas (ISSUE 8),
with replica supervision (ISSUE 10).

    # Replicas started elsewhere (examples/gpt2/serve.py, one per
    # host/chip), router in front:
    python tools/serve_fleet.py --port 9000 \
        --replica http://host-a:8000 --replica http://host-b:8000

    # SUPERVISED local replicas: serve_fleet spawns each --spawn
    # command (the {port} placeholder receives an assigned port),
    # waits for its /health to go green, and a supervisor thread then
    # watches it — a replica that dies (process exit) or wedges
    # (/health stalling past --health-stall) is quarantined in the
    # router, restarted (the fresh process re-warms its own AOT
    # ladder), and re-admitted only once /health is green again. A
    # crash-looping replica is given up on after --max-restarts and
    # left quarantined with an ERROR.
    python tools/serve_fleet.py --port 9000 \
        --spawn 'python examples/gpt2/serve.py --workdir w0 --port {port}' \
        --spawn 'python examples/gpt2/serve.py --workdir w0 --port {port}' \
        --spawn-base-port 8100

    # Telemetry-driven autoscaling (ISSUE 13): --spawn[0] is the
    # replica template; the fleet resizes between --min-replicas and
    # --max-replicas against the probe-fed signals (queue depth, KV
    # occupancy, brownout level, and per-replica /metrics TTFT p95
    # when --target-ttft-p95 is set). Scale-up green-gates the fresh
    # replica (AOT warmup finishes before it joins); scale-down is
    # always drain-first. A supervisor incident pauses all scaling
    # (the crash-loop guard).
    python tools/serve_fleet.py --port 9000 --autoscale \
        --spawn 'python examples/gpt2/serve.py --workdir w0 --port {port}' \
        --min-replicas 1 --max-replicas 4 --target-queue 4

    # Warm-standby control plane (ISSUE 16): accepted requests are
    # journaled durably; a second router on --standby-port answers
    # fenced 503s until the primary's lease heartbeat goes stale, then
    # promotes itself — rebuilding probe state from /health sweeps and
    # in-flight work from the journal (replayed token-identically by
    # seeding). A client keeps both URLs and retries the other on
    # transport failure; duplicate request_id retries dedupe.
    python tools/serve_fleet.py --port 9000 --standby \
        --standby-port 9001 --journal fleet.journal \
        --replica http://host-a:8000 --replica http://host-b:8000

    # Canary rollout: route 25% of traffic to the canary set and bank
    # a run_diff comparison of the two sets at exit (or on demand at
    # GET /canary):
    python tools/serve_fleet.py --port 9000 \
        --replica http://host-a:8000 --replica http://host-b:8000 \
        --canary http://host-c:8000 --canary-fraction 0.25 \
        --diff-out canary_diff.json

Ops verbs while running (the rollout runbook, docs/serving.md):

    curl -s :9000/replicas                      # fleet state
    curl -s -XPOST :9000/drain \
        -d '{"replica": "http://host-a:8000"}'  # stop NEW dispatch
    # ... restart host-a with the new build, then:
    curl -s -XPOST :9000/undrain \
        -d '{"replica": "http://host-a:8000"}'

The router stops dispatching to a drained (or self-draining — SIGTERM
on the replica flips its /health) replica while in-flight requests
finish on the replica itself; 503s and transport failures retry once
on another replica within a per-request budget, so a single-replica
drain under load completes with zero failed requests (test-pinned).

SIGTERM to the router itself closes the listening port and exits 0
(replicas are not touched — they drain on their own schedule). A
schema-v6 ``kind="serving"`` stats line is appended to ``--stats-out``
every ``--stats-every`` seconds.

SLO watching (ISSUE 19): the router always runs an AlertEngine
(``--slo slo.json`` loads declared objectives; the built-in defaults
are generous) doing error-budget burn-rate alerting — firing/resolve
transitions append schema-v14 ``kind="alert"`` lines to
``--alerts-out``, live state is ``GET /alerts``, ring-buffered
instrument history is ``GET /series``, and
``--synthetic-probe-every S`` runs the known-answer canary prober
through the router and each replica so a sick replica alerts ahead of
organic traffic (``tools/slo_watch.py`` is the terminal view).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--replica", action="append", default=[],
                    help="base-set replica URL (repeatable)")
    ap.add_argument("--canary", action="append", default=[],
                    help="canary-set replica URL (repeatable)")
    ap.add_argument("--port", type=int, default=9000,
                    help="router listen port (0 = auto-assign)")
    ap.add_argument("--probe-interval", type=float, default=0.5)
    ap.add_argument("--request-timeout", type=float, default=120.0)
    ap.add_argument("--retry-budget", type=float, default=10.0)
    ap.add_argument("--canary-fraction", type=float, default=0.25,
                    help="traffic share for the canary set")
    ap.add_argument("--stats-every", type=float, default=10.0,
                    help="seconds between stats lines (0 disables)")
    ap.add_argument("--stats-out", default="",
                    help="append stats lines here (default stderr)")
    ap.add_argument("--diff-out", default="",
                    help="write the base-vs-canary run_diff doc here "
                         "at exit (needs --canary)")
    ap.add_argument("--spawn", action="append", default=[],
                    help="spawn + SUPERVISE a local replica from this "
                         "command ({port} placeholder; repeatable)")
    ap.add_argument("--spawn-base-port", type=int, default=8100,
                    help="first port for --spawn replicas")
    ap.add_argument("--spawn-warm-timeout", type=float, default=600.0,
                    help="seconds to wait for a spawned replica's "
                         "/health to go green at startup")
    ap.add_argument("--health-stall", type=float, default=15.0,
                    help="supervisor: /health silent this long -> "
                         "restart the replica")
    ap.add_argument("--max-restarts", type=int, default=5,
                    help="supervisor: give up on a crash-looping "
                         "replica after this many restarts")
    ap.add_argument("--max-retries", type=int, default=2,
                    help="bounded retry: re-dispatches per request")
    ap.add_argument("--hedge-after", type=float, default=0.0,
                    help=">0: hedged dispatch for p99 — resend a "
                         "request unanswered this long (seconds)")
    ap.add_argument("--eject-after", type=int, default=3,
                    help="circuit breaker: consecutive dispatch "
                         "failures before ejecting a replica")
    ap.add_argument("--eject-cooldown", type=float, default=3.0,
                    help="circuit breaker: seconds ejected before the "
                         "half-open probe")
    ap.add_argument("--autoscale", action="store_true",
                    help="ISSUE 13: run the telemetry-driven "
                         "autoscaler — --spawn[0] is the replica "
                         "template; the fleet resizes between "
                         "--min-replicas and --max-replicas against "
                         "the target signals, scale-down drain-first")
    ap.add_argument("--min-replicas", type=int, default=1)
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--target-queue", type=float, default=4.0,
                    help="autoscaler: mean queued requests per "
                         "eligible replica before scaling up")
    ap.add_argument("--target-kv", type=float, default=0.85,
                    help="autoscaler: mean KV occupancy before "
                         "scaling up")
    ap.add_argument("--target-ttft-p95", type=float, default=0.0,
                    help="autoscaler: worst-replica TTFT p95 seconds "
                         "before scaling up (0 disables the signal)")
    ap.add_argument("--scale-hold", type=float, default=5.0,
                    help="autoscaler: min seconds between actions")
    ap.add_argument("--scale-down-idle", type=float, default=30.0,
                    help="autoscaler: sustained-idle seconds before a "
                         "drain-first scale-down")
    ap.add_argument("--journal", default="",
                    help="ISSUE 16: durable request journal (JSONL). "
                         "Accepted requests append an intent before "
                         "dispatch; a restarted router replays the "
                         "incomplete ones (token-identical by "
                         "seeding), and duplicate request_id retries "
                         "dedupe to the original tokens")
    ap.add_argument("--standby", action="store_true",
                    help="ISSUE 16: run a warm-standby router pair "
                         "over the fleet — the standby tails the "
                         "journal, answers fenced 503s until the "
                         "primary's lease heartbeat goes stale, then "
                         "promotes itself (monotonic fencing token: "
                         "a stalled-then-revived primary refuses its "
                         "own dispatches). Needs --journal")
    ap.add_argument("--standby-port", type=int, default=0,
                    help="standby router listen port (0 = auto)")
    ap.add_argument("--lease", default="",
                    help="active-router lease file (default: "
                         "<journal>.lease)")
    ap.add_argument("--heartbeat-miss", type=float, default=2.0,
                    help="standby: promote after the primary's lease "
                         "heartbeat is stale this many seconds")
    ap.add_argument("--slo", default="",
                    help="ISSUE 19: SLO config JSON (slo.json) for the "
                         "router's AlertEngine (default: built-in "
                         "generous objectives)")
    ap.add_argument("--alerts-out", default="",
                    help="append schema-v14 kind=\"alert\" firing/"
                         "resolve lines here (JSONL, fsync per line)")
    ap.add_argument("--synthetic-probe-every", type=float, default=0.0,
                    help="ISSUE 19: >0 runs the canary prober — "
                         "deterministic known-answer requests through "
                         "the router AND each replica frontend at this "
                         "cadence (seconds), feeding the AlertEngine "
                         "ahead of organic traffic; 0 disables")
    ap.add_argument("--no-affinity", action="store_true",
                    help="disable prefix-affinity dispatch (ISSUE 12; "
                         "on by default — the router prefers the "
                         "replica already caching the prompt's prefix "
                         "chain, load-guarded)")
    ap.add_argument("--affinity-load-gap", type=float, default=2.0,
                    help="affinity only wins while the chain-holder's "
                         "load score is within this gap of the "
                         "least-loaded replica")
    args = ap.parse_args(argv)
    if not args.replica and not args.spawn:
        ap.error("at least one --replica URL or --spawn command is "
                 "required")
    if args.diff_out and not args.canary:
        ap.error("--diff-out needs a --canary set to compare against")
    if args.autoscale and not args.spawn:
        ap.error("--autoscale needs a --spawn command to use as the "
                 "replica template")
    if args.standby and not args.journal:
        ap.error("--standby needs --journal (the standby rebuilds "
                 "in-flight work from the journal at takeover)")
    if args.standby and (args.canary or args.autoscale):
        ap.error("--standby does not compose with --canary/--autoscale "
                 "yet (the pair owns router lifecycle)")
    if args.standby and (args.slo or args.alerts_out):
        ap.error("--standby does not compose with --slo/--alerts-out "
                 "yet (the pair constructs both routers itself)")

    from tensorflow_examples_tpu.serving.router import (
        Router,
        RouterConfig,
        RouterFrontend,
        _get_json,
    )
    from tensorflow_examples_tpu.serving.supervisor import (
        Autoscaler,
        AutoscalerConfig,
        ProcessReplica,
        Supervisor,
    )

    spawned = []
    try:
        for i, cmd in enumerate(args.spawn):
            rep = ProcessReplica(
                cmd, port=args.spawn_base_port + i
            ).start()
            spawned.append(rep)
        for rep in spawned:
            deadline = time.monotonic() + args.spawn_warm_timeout
            while time.monotonic() < deadline:
                # ANY dead sibling ends start-up now, not after this
                # replica's warm-up: on a TPU host the second process
                # to reach for the chip dies within seconds.
                dead = [r for r in spawned if not r.alive()]
                if dead:
                    raise SystemExit(
                        "spawned replica(s) exited before /health went "
                        "green: "
                        + ", ".join(
                            f"{r.url} (exit {r.exit_code})" for r in dead
                        )
                        + ". A chip belongs to one process: --spawn "
                        "replicas get no device assignment, so on a TPU "
                        "host every one after the first fails at "
                        "start-up. Run several replicas in one process "
                        "(tools/serve_bench.py --router) or spawn "
                        "--device=cpu replicas (docs/serving.md)."
                    )
                status, body = _get_json(rep.url + "/health", 2.0)
                if status == 200 and body.get("ok"):
                    print(f"replica {rep.url} green", file=sys.stderr)
                    break
                time.sleep(0.5)
            else:
                raise SystemExit(
                    f"spawned replica {rep.url} not green within "
                    f"{args.spawn_warm_timeout:.0f}s"
                )
    except BaseException:
        # A failed startup must not orphan the replicas already
        # spawned — they hold their ports (and devices) with no
        # supervisor attached.
        for rep in spawned:
            rep.close()
        raise

    replica_urls = args.replica + [rep.url for rep in spawned]
    cfg = RouterConfig(
        probe_interval_s=args.probe_interval,
        request_timeout_s=args.request_timeout,
        retry_budget_s=args.retry_budget,
        max_retries=args.max_retries,
        hedge_after_s=args.hedge_after,
        eject_after=args.eject_after,
        eject_cooldown_s=args.eject_cooldown,
        canary_fraction=args.canary_fraction,
        prefix_affinity=not args.no_affinity,
        affinity_load_gap=args.affinity_load_gap,
    )
    pair = None
    journal = None
    if args.standby:
        # ISSUE 16: warm-standby control plane. The pair owns both
        # routers, the journal and the lease; the primary serves
        # --port, the standby answers fenced 503s on --standby-port
        # until it promotes itself on missed heartbeat.
        from tensorflow_examples_tpu.serving.chaos import RouterPair

        pair = RouterPair(
            replica_urls,
            journal_path=args.journal,
            lease_path=args.lease or args.journal + ".lease",
            router_cfg=cfg,
            primary_port=args.port,
            standby_port=args.standby_port,
            miss_budget_s=args.heartbeat_miss,
        ).start()
        router = pair.primary
        if pair.replayed_at_start:
            print(
                f"journal: replayed {pair.replayed_at_start} "
                "incomplete intent(s) from a previous incarnation",
                file=sys.stderr,
            )
    else:
        if args.journal:
            from tensorflow_examples_tpu.serving.journal import (
                RequestJournal,
            )

            journal = RequestJournal(args.journal)
            journal.refresh()
        slo_cfg = None
        if args.slo:
            from tensorflow_examples_tpu.telemetry.slo import SLOConfig

            slo_cfg = SLOConfig.load(args.slo)
            print(
                f"slo: {len(slo_cfg.objectives)} objective(s) from "
                f"{args.slo}",
                file=sys.stderr,
            )
        router = Router(
            replica_urls, canary=args.canary, cfg=cfg, journal=journal,
            slo_cfg=slo_cfg, alert_path=args.alerts_out or None,
        ).start()
        if journal is not None:
            replayed = router.replay_incomplete()
            if replayed:
                print(
                    f"journal: replayed {replayed} incomplete "
                    "intent(s) from a previous incarnation",
                    file=sys.stderr,
                )
    supervisor = None
    if spawned:
        supervisor = Supervisor(
            router,
            spawned,
            poll_s=1.0,
            health_stall_s=args.health_stall,
            warm_timeout_s=args.spawn_warm_timeout,
            max_restarts=args.max_restarts,
        ).start()
        if pair is not None:
            # Takeover re-points supervision at the promoted standby.
            pair.supervisor = supervisor
    autoscaler = None
    if args.autoscale:
        # The spawn template: --spawn[0]'s command at the next free
        # port in the spawn range. ProcessReplica.start returns as
        # soon as the process exists; the autoscaler's green gate then
        # waits for the replica's own AOT warmup to finish (/health ok)
        # before it ever joins the router.
        next_port = [args.spawn_base_port + len(args.spawn)]

        def spawn_replica(idx):
            port = next_port[0]
            next_port[0] += 1
            return ProcessReplica(args.spawn[0], port=port).start()

        autoscaler = Autoscaler(
            router,
            supervisor,
            spawn_replica,
            alerts=router.alerts,  # firing SLO alerts = advisory hot
            cfg=AutoscalerConfig(
                min_replicas=args.min_replicas,
                max_replicas=args.max_replicas,
                target_queue_depth=args.target_queue,
                target_kv_occupancy=args.target_kv,
                target_ttft_p95_s=args.target_ttft_p95,
                hold_s=args.scale_hold,
                scale_down_idle_s=args.scale_down_idle,
                warm_timeout_s=args.spawn_warm_timeout,
            ),
        ).start()
        print(
            f"autoscaler on: {args.min_replicas}..{args.max_replicas} "
            f"replicas, targets queue<{args.target_queue} "
            f"kv<{args.target_kv} ttft_p95<"
            f"{args.target_ttft_p95 or 'off'}",
            file=sys.stderr,
        )
    if pair is not None:
        frontend = pair.primary_frontend  # started by pair.start()
        print(
            f"standby router on :{pair.standby_frontend.port} "
            f"(fenced; promotes after {args.heartbeat_miss:.1f}s of "
            "missed heartbeats)",
            file=sys.stderr,
        )
    else:
        frontend = RouterFrontend(router, port=args.port).start()
    # Role topology (ISSUE 12): heterogeneous prefill/decode fleets are
    # first-class — say what the probe sweep actually found, so a
    # mis-roled rollout is visible before it serves.
    roles: dict = {}
    for rep in router.replicas:
        roles[rep.role] = roles.get(rep.role, 0) + 1
    print(
        f"router on :{frontend.port} over {len(replica_urls)} base + "
        f"{len(args.canary)} canary replica(s)"
        + (f", supervising {len(spawned)}" if spawned else "")
        + f"; roles {roles}; prefix affinity "
        + ("off" if args.no_affinity else "on"),
        file=sys.stderr,
    )
    prober = None
    if args.synthetic_probe_every > 0:
        # ISSUE 19: black-box canary probes through the router (the
        # client path) and against every replica directly (a router
        # would mask a single sick replica by failing over around it).
        # Probes carry the "probe" tag, so they never enter the
        # journal dedupe window or the organic counters; failures feed
        # the router's AlertEngine on the probe cadence.
        from tensorflow_examples_tpu.serving.prober import (
            CanaryProber,
            fleet_targets,
        )

        prober = CanaryProber(
            fleet_targets(
                f"http://127.0.0.1:{frontend.port}", replica_urls
            ),
            alerts=router.alerts,
            registry=router.registry,
            interval_s=args.synthetic_probe_every,
        ).start()
        print(
            f"canary prober on: {len(prober.targets)} target(s) every "
            f"{args.synthetic_probe_every:.1f}s",
            file=sys.stderr,
        )

    stop = []
    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, lambda *_: stop.append(1))

    def emit_stats():
        live = pair.active_router if pair is not None else router
        line = json.dumps(live.stats_line())
        if args.stats_out:
            with open(args.stats_out, "a") as f:
                f.write(line + "\n")
        else:
            print(line, file=sys.stderr)

    last_stats = time.monotonic()
    try:
        while not stop:
            time.sleep(0.2)
            if (
                args.stats_every > 0
                and time.monotonic() - last_stats >= args.stats_every
            ):
                emit_stats()
                last_stats = time.monotonic()
    finally:
        if prober is not None:
            prober.close()
        frontend.close()
        if autoscaler is not None:
            autoscaler.close()
        if supervisor is not None:
            supervisor.close()
        if pair is not None:
            pair.close()  # both routers + journal + lease monitor
        else:
            router.close()
            if journal is not None:
                journal.close()
        for rep in spawned:
            rep.close()
        if autoscaler is not None:
            # Replicas the autoscaler spawned after startup.
            for url, handle in list(autoscaler.supervisor.handles.items()):
                handle.close()
        if args.diff_out:
            import run_diff

            base, canary = router.canary_records()
            deltas, skipped = run_diff.diff_records(base, canary)
            doc = {
                "a_path": "router:base",
                "b_path": "router:canary",
                "ranked": deltas,
                "not_comparable": skipped,
                "regressions": sum(
                    1 for d in deltas if d["verdict"] == "regressed"
                ),
                "a": base,
                "b": canary,
            }
            doc.update({k: canary.get(k) for k in run_diff.GATE_KEYS})
            with open(args.diff_out, "w") as f:
                json.dump(doc, f, indent=2)
                f.write("\n")
            print(f"canary diff -> {args.diff_out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
