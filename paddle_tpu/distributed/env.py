"""Multi-process bootstrap — the reference's gen_nccl_id/comm-init RPC
(``operators/collective/c_gen_nccl_id_op.cc``, ``c_comm_init_op.cc``)
replaced by the JAX coordination service.

Env contract (reference role_maker.py:327 + launch.py):
  PADDLE_TRAINER_ID        this process's rank
  PADDLE_TRAINERS_NUM      world size
  PADDLE_TRAINER_ENDPOINTS comma list; endpoint 0 doubles as the
                           jax coordinator when no coordination
                           service is configured
  PADDLE_COORD_ADDR        host:port of a live coordination service
                           (distributed/coordination.py). When set,
                           rank/world/jax-coordinator are derived FROM
                           THE SERVICE — no shared filesystem, and
                           missing PADDLE_TRAINER_ID/TRAINERS_NUM are
                           assigned by the service (atomic rank
                           counter + published world size).
  PADDLE_COORD_WAL_DIR     makes the launcher-owned coordinator durable
                           (WAL + snapshots): a coordinator kill+restart
                           mid-bootstrap or mid-run resumes the rank
                           map, barrier generations, and leases instead
                           of stranding the gang.
  PADDLE_COORD_GRACE_S     how long each bootstrap/worker client re-dials
                           through a coordinator outage before surfacing
                           ConnectionError (default 30).
  PADDLE_DIST_BACKEND      optional: "cpu" forces the virtual-CPU backend
                           with gloo cross-process collectives (the test
                           fake-cluster mode, SURVEY §4); unset = chips.

After ``init_parallel_env()`` the global device view spans processes:
``jax.devices()`` shows every chip in the job, and CompiledProgram meshes
built on it run collectives over ICI within a host and DCN across hosts.
"""

import os

_initialized = False


def _env_int(name, default):
    v = os.environ.get(name)
    return int(v) if v else default


def parallel_env():
    """(rank, world_size, endpoints) from the PADDLE_* env contract."""
    eps = [e for e in os.environ.get(
        "PADDLE_TRAINER_ENDPOINTS", "").split(",") if e]
    world = _env_int("PADDLE_TRAINERS_NUM", len(eps) or 1)
    rank = _env_int("PADDLE_TRAINER_ID", 0)
    return rank, world, eps


def trainer_env(rank, endpoints, attempt=0, base_env=None):
    """The PADDLE_* env block for one trainer process — the single
    derivation point, shared by ``distributed.launch``'s initial spawn
    and every elastic reformation (a shrunk gang re-derives
    ``PADDLE_TRAINERS_NUM``/rank/endpoints here, so the two can never
    disagree). ``endpoints`` is the FULL gang endpoint list; world size
    is its length. Returns a fresh dict layered over ``base_env``."""
    endpoints = list(endpoints)
    rank = int(rank)
    if not 0 <= rank < len(endpoints):
        raise ValueError("rank %d outside the %d-endpoint gang"
                         % (rank, len(endpoints)))
    env = dict(base_env) if base_env is not None else {}
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(len(endpoints)),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "TRAINING_ROLE": "TRAINER",
        "PADDLE_RESTART_ATTEMPT": str(int(attempt)),
    })
    return env


def _coord_bootstrap():
    """(rank, world, jax_coordinator) from the coordination service.
    Rank/world come from the PADDLE_* env when the launcher set them;
    a standalone joiner without them draws a rank from the service's
    atomic counter and waits for the published world size. Rank 0
    picks a fresh port on its own host for the jax coordinator and
    publishes it — the piece that previously required endpoint 0 of a
    shared env list. All keys are namespaced by the restart attempt so
    a reformed gang can never read the previous generation's values."""
    from . import coordination as _coord
    from . import wire as _wire

    client = _coord.CoordClient(_coord.current_coord_addr())
    try:
        ns = "env/%s/" % os.environ.get("PADDLE_RESTART_ATTEMPT", "0")
        rank_s = os.environ.get("PADDLE_TRAINER_ID")
        if rank_s:
            rank = int(rank_s)
        else:
            rank = client.add(ns + "rank_counter", 1) - 1
        world_s = os.environ.get("PADDLE_TRAINERS_NUM")
        if world_s:
            world = int(world_s)
        else:
            raw = client.get(ns + "world_size", wait=True, timeout=120.0)
            if raw is None:
                raise TimeoutError(
                    "coordination service never published %sworld_size "
                    "(set PADDLE_TRAINERS_NUM or have the launcher put "
                    "it)" % ns)
            world = int(raw)
        if rank == 0:
            host = os.environ.get("PADDLE_CURRENT_ENDPOINT",
                                  "").rsplit(":", 1)[0] or "127.0.0.1"
            coordinator = "%s:%d" % (host, _wire.free_port(host))
            client.put(ns + "jax_coordinator", coordinator)
        else:
            raw = client.get(ns + "jax_coordinator", wait=True,
                             timeout=120.0)
            if raw is None:
                raise TimeoutError(
                    "rank 0 never published %sjax_coordinator" % ns)
            coordinator = raw.decode()
        return rank, world, coordinator
    finally:
        client.close()


def init_parallel_env(ndev_per_proc=None):
    """Join the job's coordination service (idempotent). Returns
    (rank, world_size). Single-process jobs return immediately."""
    global _initialized
    from . import coordination as _coord

    coord_addr = _coord.current_coord_addr()
    rank, world, eps = parallel_env()
    if world <= 1 and not coord_addr:
        return rank, world
    if _initialized:
        return rank, world
    # arm the flight recorder before any collective can wedge this
    # worker; no-op unless the launcher exported $PADDLE_FLIGHT_DIR
    from ..telemetry import flight as _flight
    _flight.start(rank=rank)
    import jax

    if os.environ.get("PADDLE_DIST_BACKEND", "").lower() == "cpu":
        # fake-cluster mode: virtual CPU devices + gloo collectives (the
        # spawn-local-subprocess test pattern, reference test_dist_base.py)
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        if ndev_per_proc is None:
            ndev_per_proc = _env_int("PADDLE_LOCAL_DEVICES", 1)
        jax.config.update("jax_num_cpu_devices", int(ndev_per_proc))
    if coord_addr:
        rank, world, coordinator = _coord_bootstrap()
        if world <= 1:
            _initialized = True
            return rank, world
    else:
        coordinator = eps[0] if eps else "127.0.0.1:12765"
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=world,
        process_id=rank,
    )
    _initialized = True
    return rank, world


def is_multiprocess():
    import jax

    try:
        return jax.process_count() > 1
    except Exception:
        return False


def wait_server_ready(endpoints, timeout=120.0, interval=0.5):
    """Block until every ``host:port`` endpoint accepts a TCP connection
    (reference ``transpiler/distribute_transpiler.py:322`` — trainers poll
    pservers; here: pollers for the PS tier / NAS controller / any
    socket-served component)."""
    import time

    from . import wire as _wire

    pending = list(endpoints)
    deadline = time.monotonic() + timeout
    while pending:
        still = []
        for i, ep in enumerate(pending):
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError("servers not ready: %s"
                                   % ",".join(still + pending[i:]))
            try:
                with _wire.connect(ep, timeout=min(2.0, remaining)):
                    pass
            except OSError:
                still.append(ep)
        pending = still
        if pending:
            if time.monotonic() > deadline:
                raise TimeoutError("servers not ready: %s" % ",".join(pending))
            time.sleep(min(interval, max(deadline - time.monotonic(), 0)))
