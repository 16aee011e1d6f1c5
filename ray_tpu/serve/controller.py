"""ServeController — the reconcile loop.

Equivalent of the reference's controller actor (ref:
python/ray/serve/_private/controller.py:74; run_control_loop :298) with
DeploymentState semantics (ref: deployment_state.py — target vs running
replicas, health checks, rolling updates, scale up/down) collapsed into
one actor. Replicas are actors the controller owns; handles discover them
via get_replicas (the long-poll analog is version-stamped polling,
ref: long_poll.py:187).
"""
from __future__ import annotations

import threading
import time
import uuid
from typing import Any, Dict, List, Optional

import ray_tpu
from ray_tpu.exceptions import ActorDiedError

from .config import HEALTHY, UNHEALTHY, UPDATING, DeploymentConfig

CONTROLLER_NAME = "SERVE_CONTROLLER"


class _ReplicaState:
    def __init__(self, handle, version: int, tag: str):
        self.handle = handle
        self.version = version
        self.tag = tag
        self.starting = True           # until first successful ping
        self.started_at = time.monotonic()
        self.last_ongoing = 0
        # preemption-notice draining (docs/FAULT_TOLERANCE.md
        # "Elasticity"): a draining replica takes no NEW requests
        # (excluded from get_replicas), finishes what it has, and is
        # killed once idle or at the drain deadline — whichever first
        self.draining = False
        self.drain_deadline = 0.0
        self.drain_marked_at = 0.0
        # prefix-cache warmth from the health ping (replica.py): the
        # session router's tie-break and the scale-down victim pick
        # both prefer keeping warm replicas
        self.cache_hit_rate = 0.0
        self.prefix_blocks_resident = 0


class _DeploymentState:
    def __init__(self, name: str, blob: bytes, init_args, init_kwargs,
                 config: DeploymentConfig):
        self.name = name
        self.blob = blob
        self.init_args = init_args
        self.init_kwargs = init_kwargs
        self.config = config
        self.version = 0
        self.replicas: List[_ReplicaState] = []
        self.status = UPDATING
        self.target = (config.autoscaling.min_replicas
                       if config.autoscaling else config.num_replicas)
        self._last_scale = 0.0
        self.deleted = False
        # traceback of a replica constructor that raised, until a replica
        # of this version comes up or the deployment is redeployed
        self.constructor_error = ""


class ServeController:
    def __init__(self, control_period_s: float = 0.5):
        self._period = control_period_s
        self._deployments: Dict[str, _DeploymentState] = {}
        # deleted-then-redeployed states drain here until their replicas die
        self._graveyard: list = []
        self._lock = threading.RLock()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._control_loop,
                                        daemon=True, name="serve-reconcile")
        self._thread.start()

    # -- API ------------------------------------------------------------------

    def deploy(self, name: str, blob: bytes, init_args, init_kwargs,
               config: DeploymentConfig) -> bool:
        with self._lock:
            st = self._deployments.get(name)
            if st is not None and st.deleted:
                self._graveyard.append(st)  # loop still owns its replicas
                st = None
            if st is None:
                st = _DeploymentState(name, blob, init_args, init_kwargs,
                                      config)
                self._deployments[name] = st
                return True
            code_changed = (blob != st.blob
                            or init_args != st.init_args
                            or init_kwargs != st.init_kwargs
                            or config.version_fields()
                            != st.config.version_fields())
            st.blob, st.init_args, st.init_kwargs = blob, init_args, init_kwargs
            st.config = config
            if not config.autoscaling:
                st.target = config.num_replicas
            if code_changed:
                st.version += 1         # triggers rolling replacement
                st.status = UPDATING
                st.constructor_error = ""
            return True

    def delete(self, name: str) -> bool:
        # mark-and-reconcile rather than pop: an in-flight _reconcile
        # holding this state must not restart replicas for a deployment
        # that no longer exists — the loop drains it and removes the entry
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return False
            st.deleted = True
            st.target = 0
        return True

    @staticmethod
    def _routable(st: _DeploymentState):
        """Replicas the router may assign work to — the ONE routability
        definition get_replicas and replica_warmth both use."""
        return [r for r in st.replicas
                if not r.starting and not r.draining
                and r.version == st.version]

    @staticmethod
    def _warmth_of(replicas) -> Dict[str, float]:
        return {r.handle._actor_id.hex(): float(r.prefix_blocks_resident)
                for r in replicas}

    def get_replicas(self, name: str, with_warmth: bool = False):
        """-> (version, max_concurrent_queries, [actor handles]) for
        routing — plus the cache-warmth map (actor hex -> resident
        prefix blocks) when ``with_warmth``, so the handle gets both in
        ONE round trip per refresh. Draining replicas are EXCLUDED: the
        router stops assigning new requests/streams the moment its next
        refresh lands, while in-flight work on them runs to
        completion."""
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return (0, 0, [], {}) if with_warmth else (0, 0, [])
            routable = self._routable(st)
            handles = [r.handle for r in routable]
            if not with_warmth:
                return (st.version, st.config.max_concurrent_queries,
                        handles)
            return (st.version, st.config.max_concurrent_queries,
                    handles, self._warmth_of(routable))

    def drain_replicas(self, actor_id_hexes, grace_s: float = 30.0) -> int:
        """Preemption-notice draining: mark every replica whose actor id
        is in ``actor_id_hexes`` (hex strings) as draining, across all
        deployments. The runtime calls this when a node gets a
        ``NODE_PREEMPTING`` event; operators/tests may call it directly
        for scripted scale-downs. Returns the number of replicas newly
        marked. Replacement replicas start on the next reconcile pass
        (draining replicas stop counting toward target), and the
        drained corpse is killed once idle or at the deadline."""
        wanted = {h.lower() for h in actor_id_hexes}
        marked = []
        deadline = time.monotonic() + max(0.0, float(grace_s))
        with self._lock:
            for st in self._deployments.values():
                for r in st.replicas:
                    if r.draining:
                        continue
                    if r.handle._actor_id.hex().lower() in wanted:
                        r.draining = True
                        r.drain_deadline = deadline
                        r.drain_marked_at = time.monotonic()
                        marked.append(r)
        for r in marked:
            # the replica reports draining in its own health ping from
            # here on (observability surface; the routing decision
            # already happened via get_replicas exclusion)
            try:
                r.handle.set_draining.options(
                    concurrency_group="control").remote(True)
            except Exception:
                pass
        return len(marked)

    def get_slo(self, name: str) -> Optional[float]:
        """The deployment's latency SLO target in seconds (None = no
        SLO). Handles fetch this once per version change and count every
        routed request into ray_tpu_serve_slo_{ok,violated}_total."""
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return None
            return getattr(st.config, "slo_target_s", None)

    def replica_warmth(self, name: str) -> Dict[str, float]:
        """actor_id hex -> CURRENT resident prefix-block count for
        every routable replica (the health-ping `cache_stats` surface).
        Resident blocks, not the cumulative hit rate, is the warmth
        signal: a cleared or freshly-restarted cache reads 0 here no
        matter what its historical ratio was. Introspection twin of the
        map `get_replicas(..., with_warmth=True)` piggybacks to the
        router."""
        with self._lock:
            st = self._deployments.get(name)
            if st is None:
                return {}
            return self._warmth_of(self._routable(st))

    def status(self) -> Dict[str, dict]:
        with self._lock:
            return {
                name: {"status": st.status, "version": st.version,
                       "target": st.target,
                       "running": sum(1 for r in st.replicas
                                      if not r.starting and not r.draining),
                       "draining": sum(1 for r in st.replicas
                                       if r.draining),
                       "cache_blocks_resident": sum(
                           r.prefix_blocks_resident for r in st.replicas),
                       "constructor_error": st.constructor_error}
                for name, st in self._deployments.items() if not st.deleted
            }

    def list_deployments(self) -> List[str]:
        with self._lock:
            return list(self._deployments)

    def ping(self) -> str:
        return "ok"

    def shutdown(self) -> bool:
        self._stop.set()
        with self._lock:
            states = list(self._deployments.values())
            self._deployments.clear()
        for st in states:
            for r in st.replicas:
                self._kill(r)
        return True

    # -- reconciliation -------------------------------------------------------

    def _control_loop(self) -> None:
        while not self._stop.is_set():
            try:
                with self._lock:
                    states = (list(self._deployments.values())
                              + list(self._graveyard))
                for st in states:
                    self._reconcile(st)
            except Exception:
                import traceback

                traceback.print_exc()
            self._stop.wait(self._period)

    def _reconcile(self, st: _DeploymentState) -> None:
        if st.deleted:
            with self._lock:
                victims = list(st.replicas)
                st.replicas.clear()
            for r in victims:
                self._kill(r, st.config.graceful_shutdown_timeout_s)
            with self._lock:
                if self._deployments.get(st.name) is st:
                    del self._deployments[st.name]
                if st in self._graveyard:
                    self._graveyard.remove(st)
            return
        self._health_check(st)
        self._autoscale(st)
        with self._lock:
            current = list(st.replicas)
            target = st.target
            version = st.version
        # drain completion: a draining replica dies the moment it is
        # idle (after at least one post-mark health ping, so a stream
        # assigned just before the mark is visible) or at the deadline.
        # It stopped counting toward target below, so its replacement
        # is already starting — notice → drain → handoff → clean exit.
        now = time.monotonic()
        for r in [r for r in current if r.draining]:
            settled = now - getattr(r, "drain_marked_at", 0.0) \
                > st.config.health_check_period_s
            idle = not r.starting and r.last_ongoing == 0 and settled
            if idle or now > r.drain_deadline:
                with self._lock:
                    if r in st.replicas:
                        st.replicas.remove(r)
                self._kill(r, st.config.graceful_shutdown_timeout_s)
                current.remove(r)
        active = [r for r in current if not r.draining]
        running = [r for r in active if not r.starting]
        # rolling update: at most one old replica replaced per cycle, and
        # only while the deployment is at healthy strength (ref:
        # deployment_state.py rolling update semantics)
        old = [r for r in running if r.version != version]
        if old and len(running) >= target:
            victim = old[0]
            with self._lock:
                if victim in st.replicas:
                    st.replicas.remove(victim)
            self._kill(victim, st.config.graceful_shutdown_timeout_s)
            active = [r for r in active if r is not victim]
        # scale up (draining replicas do not count: their capacity is
        # already promised away, so replacements start NOW)
        while len(active) < target:
            r = self._start_replica(st, version)
            if r is None:
                break
            active.append(r)
        # scale down (starting first; among running, the CACHE-COLDEST
        # goes first — killing a warm replica throws away resident
        # prefix KV that sessions pinned to it still want — then
        # newest). Warmth = CURRENT resident blocks, not the cumulative
        # hit rate: a cleared cache is cold regardless of its history
        while len(active) > target:
            victim = sorted(active,
                            key=lambda r: (not r.starting,
                                           r.prefix_blocks_resident,
                                           -r.started_at))[0]
            with self._lock:
                if victim in st.replicas:
                    st.replicas.remove(victim)
            self._kill(victim, st.config.graceful_shutdown_timeout_s)
            active.remove(victim)
        with self._lock:
            healthy = sum(1 for r in st.replicas
                          if not r.starting and not r.draining
                          and r.version == version)
            if healthy >= st.target and not old:
                st.status = HEALTHY
                st.constructor_error = ""
            elif not st.replicas:
                st.status = UNHEALTHY
            else:
                st.status = UPDATING

    def _health_check(self, st: _DeploymentState) -> None:
        with self._lock:
            replicas = list(st.replicas)
        if not replicas:
            return
        probes = [(r, r.handle.ping.options(
            concurrency_group="control").remote()) for r in replicas]
        deadline = time.monotonic() + st.config.health_check_timeout_s
        for r, ref in probes:
            timeout = max(0.1, deadline - time.monotonic())
            try:
                info = ray_tpu.get(ref, timeout=timeout)
                r.starting = False
                # autoscaling load = max(in-flight RPCs, app-reported
                # backlog): streaming/engine replicas report queue_depth
                # in the ping (replica.py) — in-flight alone undercounts
                # a deep engine queue behind one streaming call
                r.last_ongoing = max(int(info.get("ongoing", 0)),
                                     int(info.get("queue_depth", 0)))
                r.cache_hit_rate = float(info.get("cache_hit_rate", 0.0))
                r.prefix_blocks_resident = int(
                    info.get("prefix_blocks_resident", 0))
            except ActorDiedError as e:
                # a replica that died before it ever answered: its
                # constructor raised (the death cause holds its
                # traceback). Waiting out the grace below would take
                # three health-check timeouts to say the same
                with self._lock:
                    if r.starting and r.version == st.version:
                        st.constructor_error = str(e)
                    if r in st.replicas:
                        st.replicas.remove(r)
                self._kill(r, st.config.graceful_shutdown_timeout_s)
            except Exception:
                grace = st.config.health_check_timeout_s * 3
                if r.starting and time.monotonic() - r.started_at < grace:
                    continue  # still constructing
                with self._lock:
                    if r in st.replicas:
                        st.replicas.remove(r)
                self._kill(r, st.config.graceful_shutdown_timeout_s)

    def _autoscale(self, st: _DeploymentState) -> None:
        cfg = st.config.autoscaling
        if cfg is None:
            return
        with self._lock:
            running = [r for r in st.replicas
                       if not r.starting and not r.draining]
            ongoing = sum(r.last_ongoing for r in running)
        if not running:
            return
        import math

        desired = max(cfg.min_replicas,
                      min(cfg.max_replicas,
                          math.ceil(ongoing / cfg.target_ongoing_requests)))
        now = time.monotonic()
        if desired > st.target and now - st._last_scale >= cfg.upscale_delay_s:
            st.target = desired
            st._last_scale = now
        elif (desired < st.target
              and now - st._last_scale >= cfg.downscale_delay_s):
            st.target = desired
            st._last_scale = now

    # -- replica ops ----------------------------------------------------------

    def _start_replica(self, st: _DeploymentState,
                       version: int) -> Optional[_ReplicaState]:
        from .replica import Replica

        tag = f"{st.name}#{uuid.uuid4().hex[:6]}"
        opts = dict(st.config.ray_actor_options)
        opts.setdefault("num_cpus", 1.0)
        # real request parallelism must match the router's admission cap —
        # and batching only happens when requests overlap. The "control"
        # lane keeps health pings and queue-depth probes off the request
        # threads, so a saturated replica still answers its router
        # (ref: replica.py max_concurrent_queries + concurrency groups)
        opts.setdefault("max_concurrency",
                        int(st.config.max_concurrent_queries))
        # MERGE (not setdefault): user-supplied groups must not evict the
        # control lane, or every health ping / depth probe errors out
        cg = dict(opts.get("concurrency_groups") or {})
        cg.setdefault("control", 2)
        opts["concurrency_groups"] = cg
        try:
            cls = ray_tpu.remote(Replica)
            handle = cls.options(**opts).remote(
                st.blob, st.init_args, st.init_kwargs,
                st.config.user_config, st.name, tag, version)
        except Exception:
            import traceback

            traceback.print_exc()
            return None
        r = _ReplicaState(handle, version, tag)
        with self._lock:
            st.replicas.append(r)
        return r

    def _kill(self, r: _ReplicaState, grace_s: float = 5.0) -> None:
        try:
            ray_tpu.get(r.handle.shutdown.remote(), timeout=grace_s)
        except Exception:
            pass
        try:
            ray_tpu.kill(r.handle)
        except Exception:
            pass


def get_or_create_controller():
    """The controller is a named detached actor shared by all drivers in
    the session (ref: serve/_private/client.py get_controller)."""
    cls = ray_tpu.remote(ServeController)
    return cls.options(name=CONTROLLER_NAME, lifetime="detached",
                       get_if_exists=True, max_restarts=1).remote()
