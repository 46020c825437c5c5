#!/usr/bin/env python
"""Serve-side chaos soak: overload + failover against a live ServeFleet.

In-process sibling of tools/chaos_soak.py for the PR-11 self-protecting
serving layer (mine_tpu/serve/admission.py, fleet.py). One run drives a
fleet through three phases, each behavior injected through the fault seams
in mine_tpu/testing/faults.py — never by monkeypatching serve code:

  warm      pre-encode W scenes, render a request per scene: the healthy
            baseline every later invariant is judged against.
  overload  FaultPlan(queue_flood=N, slow_render_ms=M): an instantaneous
            tier-0 flood against a slowed device, with critical riders and
            per-request deadlines on the low tiers. The admission ladder
            must shed/degrade tier 0 while EVERY critical request renders.
  failover  FaultPlan(shard_kill=k, shard_kill_heal_after=h): placements
            on shard k fail until h injections -> consecutive failures mark
            it dead, the engine's bounded encode retry rides each request
            through re-routing, then mark_alive re-adopts the shard. Zero
            failed requests end to end.
  session   a StreamSession (keyframe cadence K, shard-sticky key prefix)
            streams frames while its OWNER shard is force-killed
            mid-stream: the dropped keyframe MPI must transparently
            re-encode from the pixels riding each interpolated request —
            zero failed frames, and strictly more sync encodes than the
            healthy ceil(frames/K).
  flaky_link  FaultPlan(net_latency_ms, net_drop_every, net_truncate_times)
            against policy-armed HostClients (serve.net.*): the bounded
            retry + stale-reconnect paths must absorb every injected
            drop and truncation — zero critical failures, with retry
            counters proving the chaos actually bit.
  wire      two arms over a wire-armed host pair under the flaky-link
            plan: a JSON/base64 control, then mtpu-wire1 binary framing
            with int8 wire quantization + the owner-coalescer. Zero
            critical failures on both arms, truncated binary frames
            rejected by the tripwires and RETRIED (never crashed on),
            at least one coalesced same-owner batch, and strictly fewer
            upload bytes than the JSON arm.
  partition an asymmetric partition matrix (net_partition="h1>n1,h2>n0")
            across three RingFronts over the same two hosts: suspicion
            stays FRONT-LOCAL (membership is single-writer), every view
            resolves exactly one alive owner per key (no split-brain),
            the unpartitioned front keeps serving, and the heal
            re-converges all owner maps after revive_probes clean
            heartbeats.
  hosts     the multi-host ring (serve/ring.py + hostnet.py, --hosts N,
            0 skips): ONE packed AOT artifact is built in a subprocess
            (hostnet --build-artifact), N hosts boot from it — each must
            report aot_loads > 0 with aot_compiles == 0 (zero-compile
            join) — and a RingFront routes floods at them. Synthetic
            admission pressure drives the hysteretic Autoscaler to spawn
            host N+1 (the trail must be non-oscillating: no grow/shrink
            flapping), then the owner host of a hot key takes a REAL
            SIGTERM mid-flood while critical requests carry their source
            image: the drain hands the key range back ring-wise, every
            critical request still renders (failover hosts sync-encode
            from the riding pixels), the killed host exits 0 leaving an
            incident bundle, and a replacement joins — again with zero
            live compiles.

Every line of output is "phase=<name> key=value ..." (parseable); the run
exits NONZERO if any invariant breaks:

  * a critical (tier >= 2) request sheds, expires, or errors — ever;
  * the overload phase fails to actually overload (no shed AND no degrade
    means the harness lost its teeth, which must be loud, not green);
  * the failover phase ends with a dead shard un-revived, a lost entry,
    or any failed request;
  * the session phase drops a frame, fails to re-encode after the owner
    kill, or ends with the session table non-empty;
  * the flaky-link phase leaks a single failure to the critical tier, or
    finishes with zero retries (the injection never bit);
  * the wire phase fails a critical request on either arm, crashes on a
    truncated binary frame instead of retrying it, coalesces nothing, or
    ships MORE upload bytes on the binary arm than the JSON one;
  * the partition phase sees a front write ring membership, a key with
    no alive owner in any view, suspicion on the unpartitioned front,
    or an owner map that fails to re-converge after the heal;
  * the hosts phase boots a host with live compiles, lets a critical
    request fail through the SIGTERM, leaves the killed host's key range
    uncovered, oscillates the autoscale trail, or loses the incident
    bundle the drain must dump;
  * the funneled event stream fails mtpu-ev1 strict validation;
  * the flight recorder (armed for the whole soak) captured no incident
    bundle — the admission shed and shard kill are watched trigger kinds,
    so a clean run MUST leave bundles behind — or any captured bundle
    fails to render through tools/postmortem.py. A violation additionally
    force-dumps a bundle carrying the failing invariant as its trigger.

Usage (CPU is fine — the point is the control plane, not render speed):

  JAX_PLATFORMS=cpu python tools/serve_chaos_soak.py \
      --flood 48 --slow-render-ms 20 --events /tmp/soak_events.jsonl
"""

import argparse
import os
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

S, HW = 4, 8
POSE = np.eye(4, dtype=np.float32)


def _encode_fn(img_hwc):
    """Deterministic synthetic encoder: image bytes -> a fixed tiny MPI
    (the soak exercises the serving control plane, not the network)."""
    rng = np.random.RandomState(int(np.asarray(img_hwc).sum()) % 1000)
    p = rng.uniform(-1, 1, (S, 4, HW, HW)).astype(np.float32)
    return (p[:, 0:3], p[:, 3:4],
            np.linspace(1.0, 0.2, S, dtype=np.float32),
            np.eye(3, dtype=np.float32))


def _image(seed):
    return np.full((HW, HW, 3), float(seed), np.float32)


def _key(shard, n, tag):
    """An image id owned by `shard` under an `n`-way key-range partition
    (leading 8 hex digits are the key position — serve/fleet.py)."""
    return f"{(shard * 2 ** 32) // n + 1:08x}{tag}"


def _settle(futs, timeout):
    """Wait for every future; -> list of ("ok" | exception-class-name)."""
    import concurrent.futures as cf
    cf.wait([f for _, f in futs], timeout=timeout)
    out = []
    for tier, f in futs:
        if not f.done():
            out.append((tier, "Timeout"))
        elif f.exception() is not None:
            out.append((tier, type(f.exception()).__name__))
        else:
            out.append((tier, "ok"))
    return out


def run_hosts_phase(args, check, events_path):
    """Multi-host ring phase: subprocess hosts booted from ONE packed AOT
    artifact, RingFront routing, a pressure-driven scale-up, and a real
    SIGTERM through the owner host of live critical traffic. Children
    inherit MINE_TPU_TELEMETRY_EVENTS so their join/drain events funnel
    into the parent's stream for the strict-validation pass."""
    import signal
    import subprocess
    import time

    from mine_tpu.serve import HostClient, HostRing, RingFront
    from mine_tpu.serve.admission import TIER_CRITICAL, TIER_STANDARD
    from mine_tpu.serve.ring import Autoscaler, pressure_score

    workdir = tempfile.mkdtemp(prefix="serve_soak_hosts_")
    artifact = os.path.join(workdir, "aot.pack.tar")
    env = dict(os.environ, PYTHONPATH=REPO,
               MINE_TPU_TELEMETRY_EVENTS=events_path)
    hostnet = [sys.executable, "-m", "mine_tpu.serve.hostnet"]
    warm_key, warm_seed = _key(0, 1, "hostwarm"), 7

    # one artifact for every host: built through the SAME fleet code path
    # hosts boot with, so the program keys are compatible by construction
    build = subprocess.run(
        hostnet + ["--host-id", "builder", "--build-artifact", artifact,
                   "--cache-shards", "1", "--warm-key", warm_key,
                   "--warm-seed", str(warm_seed)],
        env=env, cwd=REPO, capture_output=True, text=True,
        timeout=args.timeout_s)
    check(build.returncode == 0 and os.path.exists(artifact),
          f"artifact build failed rc={build.returncode}: "
          f"{build.stderr.strip()[-300:]}")
    built = [ln for ln in build.stdout.splitlines() if "built=1" in ln]
    print(f"phase=hosts {built[0] if built else 'built=?'}", flush=True)

    procs, addrs = {}, {}
    ring = HostRing()
    front = RingFront(ring, {})

    def _boot(host_id):
        """Spawn a host from the packed artifact, assert the zero-compile
        join evidence on its ready line, and wire it into the front."""
        p = subprocess.Popen(
            hostnet + ["--host-id", host_id, "--port", "0",
                       "--aot-artifact", artifact,
                       "--warm-key", warm_key,
                       "--warm-seed", str(warm_seed),
                       "--drain-timeout-s", "10",
                       "--incidents-dir",
                       os.path.join(workdir, f"incidents_{host_id}")],
            env=env, cwd=REPO, stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True, bufsize=1)
        procs[host_id] = p
        info = {}
        deadline = time.monotonic() + args.timeout_s
        while time.monotonic() < deadline:
            line = p.stdout.readline()
            if not line:
                break
            fields = dict(kv.split("=", 1)
                          for kv in line.split() if "=" in kv)
            if fields.get("ready") == "1":
                info = fields
                break
        check(info.get("ready") == "1",
              f"host {host_id} never reached ready")
        if info.get("ready") != "1":
            return info
        loads = int(info.get("aot_loads", 0))
        compiles = int(info.get("aot_compiles", -1))
        check(loads > 0 and compiles == 0,
              f"host {host_id} joined with aot_loads={loads} "
              f"aot_compiles={compiles} (expected a zero-compile join "
              f"from the packed artifact)")
        addrs[host_id] = f"127.0.0.1:{info['port']}"
        front.add_host(host_id,
                       HostClient(addrs[host_id],
                                  timeout_s=args.timeout_s),
                       aot_loads=loads, aot_compiles=compiles)
        return info

    try:
        for i in range(args.hosts):
            _boot(f"h{i}")
        print(f"phase=hosts hosts={len(ring.alive())} "
              f"coverage={ring.coverage():.2f} artifact_boots={len(procs)}",
              flush=True)

        # keys spread across the ring; every request carries its source
        # image so ANY host can sync-encode a key it never owned — the
        # zero-critical-failure mechanism through the SIGTERM below
        mh_keys = [_key(i, 8, f"mh{i}") for i in range(8)]
        mh_imgs = {k: _image(40 + i) for i, k in enumerate(mh_keys)}

        # synthetic admission pressure drives the hysteretic autoscaler:
        # two consecutive over-threshold evals grow the ring by ONE host
        # (the actuator is a real subprocess spawn), the relieved score
        # then sits in the deadband — the trail must show exactly one
        # grow and no flapping
        pressure = {"admission": 2.0}
        grown, trail = [], []

        def _grow(target):
            hid = f"h{len(procs)}"
            _boot(hid)
            grown.append(hid)
            pressure["admission"] = 0.8  # relieved into the deadband

        scaler = Autoscaler(
            min_hosts=args.hosts, max_hosts=args.hosts + 1, evals=2,
            hysteresis=0.5, cooldown_s=5.0,
            score_fn=lambda: pressure_score(
                admission=pressure["admission"],
                remote_frac=front.remote_route_fraction()),
            hosts_fn=lambda: len(ring.alive()), grow_fn=_grow)
        for _ in range(5):
            flood = _settle(
                [(TIER_STANDARD, front.submit(k, POSE, image=mh_imgs[k]))
                 for k in mh_keys], args.timeout_s)
            check(all(v == "ok" for _, v in flood),
                  f"ring flood failed pre-kill: {flood}")
            action = scaler.evaluate()
            if action is not None:
                trail.append(action)
        check(grown and len(ring.alive()) == args.hosts + 1,
              f"autoscaler never grew the ring (trail={trail})")
        check(trail == ["grow"],
              f"autoscale trail oscillated or overshot: {trail}")

        # SIGTERM the alive owner of a hot key mid-flood, critical tier:
        # the drain 503s new arrivals, the front re-resolves ring-wise,
        # and the riding image lets the failover host serve the key
        victim = ring.owner(mh_keys[0])
        vic_proc = procs[victim]
        futs = []
        for j in range(args.host_flood):
            if j == args.host_flood // 3:
                vic_proc.send_signal(signal.SIGTERM)
            k = mh_keys[j % len(mh_keys)]
            futs.append((TIER_CRITICAL, front.submit(
                k, POSE, tier=TIER_CRITICAL, image=mh_imgs[k])))
            time.sleep(0.01)
        outcomes = _settle(futs, args.timeout_s)
        crit_bad = [v for _, v in outcomes if v != "ok"]
        check(not crit_bad,
              f"critical requests failed through the host kill: "
              f"{crit_bad}")
        vic_proc.wait(timeout=args.timeout_s)
        check(vic_proc.returncode == 0,
              f"killed host {victim} exited {vic_proc.returncode} "
              f"(drain should exit 0)")
        vdir = os.path.join(workdir, f"incidents_{victim}")
        vbundles = os.listdir(vdir) if os.path.isdir(vdir) else []
        check(bool(vbundles),
              f"killed host {victim} left no incident bundle in {vdir}")
        check(ring.state(victim) in ("draining", "dead"),
              f"ring never observed {victim} leaving: "
              f"{ring.state(victim)}")
        # the dead host's key range must be re-covered: every probe key
        # resolves to exactly one alive owner, none of them the victim
        probe_owners = {ring.owner(_key(s, 16, "cov")) for s in range(16)}
        check(victim not in probe_owners,
              f"{victim} still owns keys after its drain")

        # a replacement joins — zero live compiles again (_boot asserts)
        _boot("r0")
        post = _settle(
            [(TIER_STANDARD, front.submit(k, POSE, image=mh_imgs[k]))
             for k in mh_keys], args.timeout_s)
        check(all(v == "ok" for _, v in post),
              f"post-replacement renders failed: {post}")
        print(f"phase=hosts victim={victim} "
              f"critical={len(futs)} served={sum(v == 'ok' for _, v in outcomes)} "
              f"grown={grown} trail={','.join(trail)} "
              f"replacement=r0 reroutes={front.reroutes} "
              f"remote_frac={front.remote_route_fraction():.3f} "
              f"bundles={len(vbundles)}", flush=True)
    finally:
        for hid, p in procs.items():
            if p.poll() is None:
                try:
                    HostClient(addrs[hid], timeout_s=5.0).drain()
                except Exception:  # noqa: BLE001 - hard-kill fallback
                    p.terminate()
        for p in procs.values():
            if p.poll() is None:
                try:
                    p.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    p.kill()
        front.close()  # emits the final ring_rebalance with the routes


def run_net_phases(args, check):
    """Wire-hardening phases (PR 19, serve.net.*): a flaky link the
    hardened client must absorb invisibly, then an asymmetric partition
    the failure detector must route around without split-brain.

    Everything is in-process — two tiny ServeFleets behind REAL
    HostServers, reached through policy-armed HostClients — and every
    failure is injected through the transport seams in testing/faults.py
    (net_request/net_truncate), never by monkeypatching hostnet."""
    from mine_tpu.serve import (HostClient, HostRing, HostServer, NetPolicy,
                                RingFront, ServeFleet)
    from mine_tpu.serve.admission import TIER_CRITICAL
    from mine_tpu.testing import faults
    from mine_tpu.testing.faults import FaultPlan

    fleets = {h: ServeFleet(cache_shards=1, max_requests=8, max_wait_ms=2.0,
                            max_bucket=8, encode_fn=_encode_fn, ops_port=0)
              for h in ("n0", "n1")}
    servers = {h: HostServer(fleets[h], h).start() for h in fleets}
    try:
        # ---- phase: flaky_link ----
        # latency + a deterministic every-3rd mid-request drop + two
        # truncated responses: the bounded retry and stale-reconnect
        # paths must absorb ALL of it — zero critical failures, and the
        # retry counters prove the injection actually bit
        policy = NetPolicy(enabled=True, connect_timeout_s=5.0,
                           read_timeout_s=args.timeout_s, retries=3,
                           backoff_ms=2.0, breaker_threshold=5,
                           breaker_reset_s=0.2)
        ring = HostRing()
        handles = {}
        for h in servers:
            ring.join(h)
            handles[h] = HostClient(f"127.0.0.1:{servers[h].port}",
                                    policy=policy, net_src="front",
                                    net_name=h)
        front = RingFront(ring, handles, policy=policy)
        try:
            nf_keys = [_key(i % 2, 2, f"net{i}")
                       for i in range(args.host_flood)]
            nf_imgs = {k: _image(300 + i) for i, k in enumerate(nf_keys)}
            faults.set_plan(FaultPlan(net_latency_ms=2, net_drop_every=3,
                                      net_truncate_times=2))
            futs = [(TIER_CRITICAL,
                     front.submit(k, POSE, tier=TIER_CRITICAL,
                                  image=nf_imgs[k])) for k in nf_keys]
            outcomes = _settle(futs, args.timeout_s)
            faults.set_plan(None)
            bad = [v for _, v in outcomes if v != "ok"]
            check(not bad,
                  f"flaky link leaked failures to critical tier: {bad}")
            retries = sum(c.retries for c in handles.values())
            reconnects = sum(c.reconnects for c in handles.values())
            check(retries > 0,
                  "flaky-link phase produced no client retries (the "
                  "injection did not bite — the harness lost its teeth)")
            print(f"phase=flaky_link requests={len(futs)} failures=0 "
                  f"retries={retries} reconnects={reconnects} "
                  f"front_failures={front.failures}", flush=True)
        finally:
            faults.set_plan(None)
            front.close()

        # ---- phase: partition ----
        # asymmetric split: front h1 cannot reach host n1, front h2
        # cannot reach host n0, the external front reaches both.
        # Suspicion must stay FRONT-LOCAL (membership single-writer), so
        # every view still resolves exactly one alive owner per key —
        # the no-split-brain property — and the heal re-converges all
        # owner maps to the pre-partition baseline
        policy_p = NetPolicy(enabled=True, retries=0, suspect_misses=2,
                             dead_misses=1000, revive_probes=2)
        fronts = {}
        for src in ("ext", "h1", "h2"):
            ring = HostRing()
            handles = {}
            for h in servers:
                ring.join(h)
                handles[h] = HostClient(f"127.0.0.1:{servers[h].port}",
                                        policy=policy_p, net_src=src,
                                        net_name=h)
            fronts[src] = RingFront(ring, handles, workers=2,
                                    policy=policy_p)
        p_keys = [_key(s, 16, f"part{s}") for s in range(16)]
        p_imgs = {k: _image(400 + i) for i, k in enumerate(p_keys)}
        try:
            baseline = {k: fronts["ext"].ring.owner(k) for k in p_keys}
            faults.set_plan(FaultPlan(net_partition="h1>n1,h2>n0"))
            for _ in range(2):  # suspect_misses rounds of heartbeats
                for f in fronts.values():
                    f.probe_once()
            check(fronts["h1"].suspects() == ["n1"],
                  f"h1 suspicion wrong: {fronts['h1'].suspects()}")
            check(fronts["h2"].suspects() == ["n0"],
                  f"h2 suspicion wrong: {fronts['h2'].suspects()}")
            check(fronts["ext"].suspects() == [],
                  f"unpartitioned front caught suspicion: "
                  f"{fronts['ext'].suspects()}")
            for name, f in fronts.items():
                check([s for _, s in f.ring.members()] ==
                      ["alive", "alive"],
                      f"front {name} wrote membership under partition "
                      f"(split-brain): {f.ring.members()}")
                avoid = frozenset(f.suspects())
                owners = {k: f.ring.owner(k, avoid=avoid) for k in p_keys}
                check(set(owners.values()) <= {"n0", "n1"},
                      f"front {name} resolved a non-member owner: "
                      f"{set(owners.values())}")
            # the unpartitioned front must keep SERVING through both
            ext_futs = [(TIER_CRITICAL,
                         fronts["ext"].submit(k, POSE, tier=TIER_CRITICAL,
                                              image=p_imgs[k]))
                        for k in p_keys[:8]]
            ext_out = _settle(ext_futs, args.timeout_s)
            check(all(v == "ok" for _, v in ext_out),
                  f"external front failed through the partition: {ext_out}")
            # heal: revive_probes clean heartbeats clear every suspicion
            faults.set_plan(None)
            for _ in range(2):
                for f in fronts.values():
                    f.probe_once()
            for name, f in fronts.items():
                check(f.suspects() == [],
                      f"front {name} still suspect after heal: "
                      f"{f.suspects()}")
                owners = {k: f.ring.owner(k) for k in p_keys}
                check(owners == baseline,
                      f"front {name} owner map did not re-converge "
                      f"after heal")
            print(f"phase=partition keys={len(p_keys)} "
                  f"served={sum(v == 'ok' for _, v in ext_out)} "
                  f"suspects_h1=n1 suspects_h2=n0 healed=1 "
                  f"probe_misses="
                  f"{sum(f.probe_misses for f in fronts.values())}",
                  flush=True)
        finally:
            faults.set_plan(None)
            for f in fronts.values():
                f.close()
    finally:
        faults.set_plan(None)
        for srv in servers.values():
            srv.drain(reason="soak")  # drain closes the fleet too


def run_wire_phase(args, check):
    """Binary wire fabric phase (PR 20, serve.wire.*): two arms over the
    same wire-armed host pair — a JSON/base64 control, then mtpu-wire1
    binary framing with int8 wire quantization AND the owner-coalescer —
    both under the PR-19 flaky-link plan (latency + truncated responses).

    Invariants: zero critical failures on EITHER arm; the truncation must
    actually bite (client retries > 0 — a truncated binary frame is
    rejected by the mtpu-wire1 tripwires and retried, never crashed on);
    the binary arm's coalescer must batch at least one same-owner group;
    and the binary arm moves strictly fewer upload bytes (bytes_tx) than
    the JSON arm for the same flood."""
    import time

    from mine_tpu.serve import (HostClient, HostRing, HostServer, NetPolicy,
                                RingFront, ServeFleet)
    from mine_tpu.serve.admission import TIER_CRITICAL
    from mine_tpu.serve.wire import WirePolicy
    from mine_tpu.telemetry import events as tevents
    from mine_tpu.testing import faults
    from mine_tpu.testing.faults import FaultPlan

    fleets = {h: ServeFleet(cache_shards=1, max_requests=8, max_wait_ms=2.0,
                            max_bucket=8, encode_fn=_encode_fn, ops_port=0)
              for h in ("w0", "w1")}
    wp = WirePolicy(format="binary", codec="int8", coalesce_ms=5.0,
                    coalesce_max=8)
    # the SERVER is always wire-armed; whether a link speaks binary is the
    # client's negotiated choice, which is exactly what the two arms vary
    servers = {h: HostServer(fleets[h], h, wire_policy=wp).start()
               for h in fleets}
    policy = NetPolicy(enabled=True, connect_timeout_s=5.0,
                       read_timeout_s=args.timeout_s, retries=3,
                       backoff_ms=2.0, breaker_threshold=50,
                       breaker_reset_s=0.2)
    w_keys = [_key(i % 2, 2, f"wire{i}") for i in range(args.host_flood)]
    w_imgs = {k: _image(500 + i) for i, k in enumerate(w_keys)}
    arms = {}
    try:
        for arm, arm_wp in (("json", None), ("bin_int8", wp)):
            ring = HostRing()
            handles = {}
            for h in servers:
                ring.join(h)
                handles[h] = HostClient(f"127.0.0.1:{servers[h].port}",
                                        policy=policy, net_src="front",
                                        net_name=h, wire_policy=arm_wp)
            front = RingFront(ring, handles, policy=policy, wire=arm_wp)
            try:
                # warm pass first: settles wire negotiation (whose one
                # /healthz would otherwise silently eat the truncation
                # budget) and pre-encodes every key, so the measured flood
                # is pure render traffic
                warm = _settle([(TIER_CRITICAL,
                                 front.submit(k, POSE, tier=TIER_CRITICAL,
                                              image=w_imgs[k]))
                                for k in w_keys], args.timeout_s)
                check(all(v == "ok" for _, v in warm),
                      f"wire arm {arm} warm pass failed: {warm}")
                tx0 = sum(c.bytes_tx for c in handles.values())
                r0 = sum(c.retries for c in handles.values())
                faults.set_plan(FaultPlan(net_latency_ms=1,
                                          net_truncate_times=2))
                t0 = time.perf_counter()
                futs = [(TIER_CRITICAL,
                         front.submit(k, POSE, tier=TIER_CRITICAL,
                                      image=w_imgs[k])) for k in w_keys]
                outcomes = _settle(futs, args.timeout_s)
                dt = time.perf_counter() - t0
                faults.set_plan(None)
                bad = [v for _, v in outcomes if v != "ok"]
                check(not bad,
                      f"wire arm {arm} leaked critical failures: {bad}")
                retries = sum(c.retries for c in handles.values()) - r0
                check(retries > 0,
                      f"wire arm {arm}: the truncation injection never bit "
                      f"(no client retries — truncated frames must be "
                      f"rejected and retried, not crashed on)")
                moved = sum(c.bytes_tx for c in handles.values()) - tx0
                coalesced = 0
                if arm_wp is not None:
                    wstats = front.stats().get("wire") or {}
                    coalesced = int(wstats.get("coalesced", 0))
                    check(coalesced > 0,
                          "binary arm coalesced no same-owner groups "
                          f"(stats={wstats})")
                arms[arm] = moved
                tevents.emit("serve.wire_point",
                             codec=("int8" if arm_wp is not None else arm),
                             views_per_sec=len(w_keys) / max(dt, 1e-9),
                             bytes_per_view=moved / max(len(w_keys), 1))
                print(f"phase=wire arm={arm} requests={len(futs)} "
                      f"failures=0 retries={retries} bytes_tx={moved} "
                      f"coalesced={coalesced}", flush=True)
            finally:
                faults.set_plan(None)
                front.close()
        check(arms["bin_int8"] < arms["json"],
              f"binary wire moved {arms['bin_int8']} upload bytes vs "
              f"JSON's {arms['json']} — the frame format saved nothing")
    finally:
        faults.set_plan(None)
        for srv in servers.values():
            srv.drain(reason="soak")  # drain closes the fleet too


def main():
    ap = argparse.ArgumentParser(
        description="serve-side chaos soak (overload + shard failover)")
    ap.add_argument("--scenes", type=int, default=4)
    ap.add_argument("--flood", type=int, default=48,
                    help="tier-0 burst size (FaultPlan.queue_flood)")
    ap.add_argument("--critical", type=int, default=6,
                    help="critical riders submitted during the flood")
    ap.add_argument("--slow-render-ms", type=int, default=20,
                    help="injected device slowdown during the overload")
    ap.add_argument("--deadline-ms", type=float, default=2000.0,
                    help="per-request deadline for the flooded low tiers")
    ap.add_argument("--shards", type=int, default=4)
    ap.add_argument("--hosts", type=int, default=2,
                    help="subprocess hosts for the multi-host ring phase "
                         "(0 skips the phase)")
    ap.add_argument("--host-flood", type=int, default=24,
                    help="requests routed through the ring during the "
                         "host-kill flood")
    ap.add_argument("--timeout-s", type=float, default=120.0)
    ap.add_argument("--events", type=str, default=None,
                    help="event-stream path (default: a temp file)")
    ap.add_argument("--incidents-dir", type=str, default=None,
                    help="flight-recorder bundle directory (default: "
                         "incidents/ next to the event stream)")
    args = ap.parse_args()

    from mine_tpu.utils import refuse_on_tpu
    refuse_on_tpu("serve_chaos_soak.py")  # an in-process fleet AND hosts

    from mine_tpu.serve import ServeFleet
    from mine_tpu.serve.admission import (TIER_BEST_EFFORT, TIER_CRITICAL,
                                          TIER_STANDARD)
    from mine_tpu.telemetry import events as tevents
    from mine_tpu.telemetry import recorder as trecorder
    from mine_tpu.testing import faults
    from mine_tpu.testing.faults import FaultPlan

    events_path = args.events or os.path.join(
        tempfile.mkdtemp(prefix="serve_soak_"), "events.jsonl")
    tevents.reset()
    tevents.configure(events_path)
    # flight recorder armed for the whole soak: the admission ladder
    # reaching shed and the shard kill are watched trigger kinds, so the
    # GREEN path must produce bundles too — and any violation force-dumps
    # one with the failing invariant in its trigger context
    incidents_dir = args.incidents_dir or os.path.join(
        os.path.dirname(os.path.abspath(events_path)), "incidents")
    rec = trecorder.configure(incidents_dir, debounce_s=1.0, keep=16,
                              config={"soak": "serve_chaos",
                                      "flood": args.flood,
                                      "shards": args.shards})
    live = {"rec": rec}  # cleared once the recorder is released

    violations = []

    def check(cond, msg):
        if not cond:
            violations.append(msg)
            print(f"phase=check VIOLATION {msg}", flush=True)
            if live["rec"] is not None:
                bundle = live["rec"].trigger(
                    "serve_soak_violation", force=True, sync=True, msg=msg)
                print(f"phase=check incident_bundle={bundle}", flush=True)

    fleet = ServeFleet(
        cache_shards=args.shards, max_requests=8, max_wait_ms=2.0,
        max_bucket=8, encode_fn=_encode_fn, slo_objective_ms=5.0,
        ops_port=0, request_deadline_ms=0.0, encode_retries=3,
        encode_backoff_ms=5.0, shard_fail_threshold=2,
        admission_enabled=True, admission_burn_max=0.0,
        admission_queue_high=8, admission_inflight_high=0,
        admission_shed_factor=2.0, recorder=rec)
    try:
        # ---- phase: warm ----
        keys = [_key(i % args.shards, args.shards, f"warm{i}")
                for i in range(args.scenes)]
        for i, k in enumerate(keys):
            fleet.engine.put(k, *_encode_fn(_image(i)))
        warm = _settle([(TIER_STANDARD, fleet.submit(k, POSE))
                        for k in keys], args.timeout_s)
        check(all(v == "ok" for _, v in warm),
              f"warm renders failed: {warm}")
        print(f"phase=warm scenes={args.scenes} "
              f"served={sum(v == 'ok' for _, v in warm)} "
              f"health={fleet.health()['status']}", flush=True)

        # ---- phase: overload ----
        faults.set_plan(FaultPlan(queue_flood=args.flood,
                                  slow_render_ms=args.slow_render_ms))
        flood_n = faults.queue_flood_n()
        futs = []
        for i in range(flood_n):
            futs.append((TIER_BEST_EFFORT, fleet.submit(
                keys[i % len(keys)], POSE, tier=TIER_BEST_EFFORT,
                deadline_ms=args.deadline_ms)))
            if i % max(1, flood_n // args.critical) == 0 \
                    and sum(t >= TIER_CRITICAL for t, _ in futs) \
                    < args.critical:
                futs.append((TIER_CRITICAL, fleet.submit(
                    keys[i % len(keys)], POSE, tier=TIER_CRITICAL)))
        outcomes = _settle(futs, args.timeout_s)
        faults.set_plan(None)
        tally = {}
        for tier, v in outcomes:
            tally[v] = tally.get(v, 0) + 1
        crit_bad = [(t, v) for t, v in outcomes
                    if t >= TIER_CRITICAL and v != "ok"]
        check(not crit_bad, f"critical requests failed: {crit_bad}")
        st = fleet.stats()
        check(st["shed"] + st["degraded"] > 0,
              "overload produced neither shed nor degraded requests "
              "(the harness did not create pressure)")
        check(tally.get("Timeout", 0) == 0,
              f"{tally.get('Timeout', 0)} futures never resolved")
        print(f"phase=overload flood={flood_n} "
              f"critical={sum(t >= TIER_CRITICAL for t, _ in futs)} "
              f"served={tally.get('ok', 0)} "
              f"shed={st['shed']} degraded={st['degraded']} "
              f"expired={st['expired']} "
              f"admission_state={fleet.admission.state} "
              f"burn={fleet.health()['error_budget_burn']}", flush=True)

        # ---- phase: failover ----
        victim = 1 % args.shards
        heal_after = fleet.cache.fail_threshold  # dies, then the seam heals
        faults.set_plan(FaultPlan(shard_kill=victim,
                                  shard_kill_heal_after=heal_after))
        fo_keys = [_key(victim, args.shards, f"fo{i}") for i in range(3)]
        fo = _settle([(TIER_STANDARD,
                       fleet.submit(k, POSE, image=_image(90 + i)))
                      for i, k in enumerate(fo_keys)], args.timeout_s)
        check(all(v == "ok" for _, v in fo),
              f"failover-phase requests failed: {fo}")
        dead = fleet.cache.dead_shards
        check(dead == [victim],
              f"expected shard {victim} dead after consecutive placement "
              f"failures, got dead={dead}")
        resident = [k for k in fo_keys if k in fleet.cache]
        check(len(resident) == len(fo_keys),
              f"entries lost during failover: {set(fo_keys) - set(resident)}")
        health_dead = fleet.health()
        check(health_dead["status"] == "degraded",
              f"healthz not degraded with a dead shard: {health_dead}")
        faults.set_plan(None)
        moved = fleet.cache.mark_alive(victim)
        check(fleet.cache.dead_shards == [],
              f"shard {victim} still dead after mark_alive")
        post = _settle([(TIER_STANDARD, fleet.submit(k, POSE))
                        for k in fo_keys], args.timeout_s)
        check(all(v == "ok" for _, v in post),
              f"post-revival renders failed: {post}")
        print(f"phase=failover victim={victim} "
              f"failovers={fleet.cache.failovers} moved={moved} "
              f"served={sum(v == 'ok' for _, v in fo + post)} "
              f"health={fleet.health()['status']}", flush=True)

        # ---- phase: session ----
        from mine_tpu.serve import SessionManager
        kf_every, n_stream = 4, 8
        sess_victim = 2 % args.shards
        manager = SessionManager(fleet, keyframe_every=kf_every)
        # explicit key prefix -> every keyframe id is OWNED by sess_victim
        # (shard-sticky streams are the property under attack here)
        session = manager.open(
            "soak", key_prefix=_key(sess_victim, args.shards, "")[:8])
        enc_before = fleet.engine.sync_encodes
        kill_at = kf_every // 2 + 1  # between keyframe 0 and keyframe K
        outcomes = []
        for i in range(n_stream):
            fut = session.process_frame(_image(200 + i), POSE)
            try:
                fut.result(timeout=args.timeout_s)
                outcomes.append("ok")
            except Exception as exc:  # noqa: BLE001 — tallied, checked below
                outcomes.append(type(exc).__name__)
            if i == kill_at - 1:
                fleet.cache.mark_dead(sess_victim)
        extra = (fleet.engine.sync_encodes - enc_before
                 - -(-n_stream // kf_every))
        check(all(v == "ok" for v in outcomes),
              f"session frames failed after owner kill: {outcomes}")
        check(session.stats()["failed_frames"] == 0,
              f"session recorded failed frames: {session.stats()}")
        check(extra > 0,
              "owner kill produced no re-encode: the dropped keyframe was "
              "never transparently re-keyed "
              f"(sync_encodes delta {fleet.engine.sync_encodes - enc_before}"
              f", healthy baseline {-(-n_stream // kf_every)})")
        session.close()
        check(len(manager) == 0,
              f"session table not empty after close: {manager.sessions()}")
        manager.close()
        fleet.cache.mark_alive(sess_victim)
        print(f"phase=session victim={sess_victim} frames={n_stream} "
              f"K={kf_every} served={sum(v == 'ok' for v in outcomes)} "
              f"re_encodes={extra} "
              f"keyframes={session.stats()['keyframes']}", flush=True)

        # ---- phases: flaky_link + partition (wire hardening) ----
        run_net_phases(args, check)

        # ---- phase: wire (binary framing + int8 + coalescing) ----
        run_wire_phase(args, check)

        # ---- phase: hosts (multi-host ring: kill + autoscale) ----
        if args.hosts > 0:
            run_hosts_phase(args, check, events_path)
    finally:
        faults.set_plan(None)
        fleet.close()
        # release BEFORE the sink closes: the worker drains pending dumps
        # on close, and their obs.incident events must land on disk
        live["rec"] = None
        trecorder.release(rec)
        tevents.reset()  # close the sink: every line on disk for validation

    problems = tevents.validate_file(events_path, strict_kinds=True)
    check(not problems, f"event stream failed strict validation: {problems}")
    kinds = {e["kind"] for e in tevents.read_events(events_path)}
    expected = ["serve.admission", "serve.shard_dead", "serve.shard_revive",
                "serve.session_start", "serve.session_keyframe",
                "serve.session_frame", "serve.session_end",
                "serve.host_suspect", "serve.wire_point", "obs.incident"]
    if args.hosts > 0:
        expected += ["serve.host_join", "serve.host_drain",
                     "serve.autoscale", "serve.ring_rebalance"]
    for want in expected:
        check(want in kinds, f"expected a {want} event in the stream")

    # the black box must have caught the soak's own chaos (admission shed
    # and the shard kill are watched kinds), and every bundle must be a
    # complete, postmortem-renderable capture — the end-to-end proof that
    # an on-call human gets a readable story out of this fleet
    listing = rec.list_incidents()
    check(listing["incidents"],
          f"no incident bundles captured in {incidents_dir}")
    import subprocess
    for inc in listing["incidents"]:
        pm = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "postmortem.py"),
             inc["path"]], capture_output=True, text=True)
        check(pm.returncode == 0,
              f"postmortem failed on {inc['path']} (rc={pm.returncode}): "
              f"{pm.stderr.strip()[:400]}")
    print(f"phase=incidents bundles={len(listing['incidents'])} "
          f"triggers={listing['recorder']['triggers']} "
          f"suppressed={listing['recorder']['suppressed']} "
          f"dir={incidents_dir}", flush=True)

    if violations:
        print(f"phase=done SOAK FAIL violations={len(violations)}",
              file=sys.stderr, flush=True)
        for v in violations:
            print(f"  - {v}", file=sys.stderr)
        return 1
    print(f"phase=done SOAK OK events={events_path}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
