#!/usr/bin/env python
"""Chaos driver for the durable coordination service.

Each scenario SIGKILLs a standalone coordinator
(``python -m paddle_tpu.distributed.coordination``) at the worst
possible moment and restarts it on the SAME port against the SAME
``--wal-dir``, then asserts the system on top of it never noticed
beyond a bounded stall:

  barrier  kill between the two arrivals of a world-2 barrier — the
           journaled arrival survives, the blocked waiter re-dials,
           and both ranks are released with the SAME generation.
  lease    kill while a lease keeper renews a fleet-style membership
           key — the WAL-persisted wall deadline plus the keeper's
           post-reconnect replay keep the member live well past the
           TTL it held when the server died.

(The fleet's story - coordinator crash under closed-loop serving
traffic, zero lost - is ``tests/test_chaos.py::
test_fleet_rides_out_coordinator_crash``, in-process.)

Usage: python tools/chaos.py [barrier|lease|all]
Exit code 0 = every scenario held its invariant; one JSON line per
scenario on stdout.
"""

import argparse
import json
import os
import re
import signal
import subprocess
import sys
import tempfile
import threading
import time

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# printed by coordination.main() once the socket is bound
_BANNER = re.compile(r"coordination service at ([^\s:]+):(\d+) "
                     r"epoch=(\d+)")


def _spawn(wal_dir, port=0, timeout=120.0):
    """Start a coordinator subprocess; block until its stdout banner
    names the bound endpoint. Returns (proc, addr, port, epoch)."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m",
         "paddle_tpu.distributed.coordination",
         "--port", str(port), "--wal-dir", wal_dir],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        cwd=_REPO, env=env, text=True)
    # watchdog: a coordinator that never prints (import wedge, port
    # clash) would park readline() forever — kill it at the deadline
    # so the read returns EOF and we can raise with context
    watchdog = threading.Timer(timeout, proc.kill)
    watchdog.daemon = True
    watchdog.start()
    try:
        line = proc.stdout.readline()
    finally:
        watchdog.cancel()
    m = _BANNER.search(line or "")
    if not m:
        proc.kill()
        proc.wait()
        raise RuntimeError(
            "coordinator subprocess never announced its endpoint "
            "(got %r)" % (line,))
    return (proc, "%s:%s" % (m.group(1), m.group(2)),
            int(m.group(2)), int(m.group(3)))


def _kill9(proc):
    """Simulated power cut: SIGKILL, no drain, no final snapshot."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGKILL)
    proc.wait()


def scenario_barrier():
    """Kill -9 between the two arrivals of a world-2 barrier."""
    from paddle_tpu.distributed.coordination import CoordClient

    wal = tempfile.mkdtemp(prefix="chaos_barrier_")
    proc, addr, port, epoch0 = _spawn(wal)
    a = CoordClient(addr, grace=120.0)
    b = CoordClient(addr, grace=120.0)
    got = {}
    try:
        t = threading.Thread(
            target=lambda: got.__setitem__(
                "a", a.barrier("chaos/bar", 2, "rank-a", timeout=240)),
            daemon=True)
        t.start()
        time.sleep(1.0)      # rank-a's arrival is journaled; it blocks
        _kill9(proc)
        proc, _, _, epoch1 = _spawn(wal, port=port)
        assert epoch1 == epoch0 + 1, (epoch0, epoch1)
        got["b"] = b.barrier("chaos/bar", 2, "rank-b", timeout=240)
        t.join(240)
        assert not t.is_alive(), "rank-a never released"
        assert got.get("a") == got["b"], got
        # the blocked waiter crossed the restart: its client saw the
        # new epoch in the re-dial handshake
        assert a.server_epoch == epoch1, (a.server_epoch, epoch1)
        return {"scenario": "barrier", "ok": True,
                "generation": got["b"],
                "epochs": [epoch0, epoch1]}
    finally:
        a.close()
        b.close()
        _kill9(proc)


def scenario_lease():
    """Kill -9 while a lease keeper renews a membership key."""
    from paddle_tpu.distributed.coordination import CoordClient

    wal = tempfile.mkdtemp(prefix="chaos_lease_")
    proc, addr, port, epoch0 = _spawn(wal)
    cli = CoordClient(addr, grace=120.0)
    key = "chaos/members/m0"
    try:
        cli.put(key, b"alive")
        cli.start_lease_keeper(key, ttl=4.0, interval=0.5)
        assert cli.live_members("chaos/members/") == [key]
        t_kill = time.monotonic()
        _kill9(proc)
        proc, _, _, epoch1 = _spawn(wal, port=port)
        # let a post-restart beat land, and stand well past the TTL
        # the member held when the server died
        time.sleep(max(3.0, t_kill + 6.0 - time.monotonic()))
        live = cli.live_members("chaos/members/")
        held_s = time.monotonic() - t_kill
        assert key in live, (live, held_s)
        assert cli.get(key) == b"alive"
        assert cli.server_epoch == epoch1, (cli.server_epoch, epoch1)
        return {"scenario": "lease", "ok": True,
                "held_through_outage_s": round(held_s, 2),
                "epochs": [epoch0, epoch1]}
    finally:
        cli.close()
        _kill9(proc)


_SCENARIOS = {"barrier": scenario_barrier,
              "lease": scenario_lease}


def main(argv=None):
    p = argparse.ArgumentParser(
        prog="python tools/chaos.py", description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    p.add_argument("scenario", nargs="?", default="all",
                   choices=sorted(_SCENARIOS) + ["all"])
    args = p.parse_args(argv)
    names = sorted(_SCENARIOS) if args.scenario == "all" \
        else [args.scenario]
    for name in names:
        res = _SCENARIOS[name]()
        print(json.dumps(res))
    return 0


if __name__ == "__main__":
    sys.exit(main())
