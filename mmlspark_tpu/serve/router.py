"""serve.router — the fleet front: route, balance, reroute, drain.

A :class:`FleetRouter` is a thin synchronous proxy in front of N replica
:class:`~mmlspark_tpu.serve.app.ServingApp` processes (spawned via
``serve/replica.py`` or attached by URL).  It owns no model state — the
replicas batch, dispatch, and hot-swap on their own — so the router's
job is purely placement:

- **least-loaded routing** — each replica handle counts its in-flight
  proxied requests; a request goes to the healthy, non-draining replica
  serving its tenant with the lowest count;
- **health** — a background loop polls every replica's ``/readyz``;
  transport failures bump a fail streak that marks the replica unhealthy
  until the next successful poll;
- **SLO/drift rerouting** — the same loop polls ``/driftz`` and reads
  each tenant's burn-rate alerts (obs/quality.py) and active drift
  alarms.  A replica burning or drifting on a tenant gets a routing
  penalty for THAT tenant only, steering new traffic to clean replicas
  while the hot one recovers; when every candidate is burning, the
  router sheds (429) instead of piling on;
- **admission reuse** — per-tenant concurrency caps and the
  stop-accepting/flush-in-flight drain come from the SAME
  :class:`AdmissionController` machinery the replicas use
  (:meth:`~AdmissionController.admit_inline`), not a reimplementation;
- **rolling swap** — ``POST /admin/swap`` walks the replicas serving the
  tenant ONE at a time: mark the replica draining (new traffic avoids
  it), forward the swap (the replica's own flip→drain makes it
  zero-downtime locally), clear the mark, move on.  Other tenants keep
  full fleet capacity throughout.

Shutdown is drain-or-kill: admission drains the front, then every
spawned replica gets SIGTERM (its graceful path) and SIGKILL only after
a timeout — no orphaned serving processes (analyzer rule SRV002).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from typing import Dict, List, Optional, Sequence, Tuple

from mmlspark_tpu import obs
from mmlspark_tpu.io.http.http_schema import HTTPRequestData, HTTPResponseData
from mmlspark_tpu.io.http.serving import HTTPServer
from mmlspark_tpu.serve.admission import AdmissionController

_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..")
)

#: Routing penalty (in in-flight-request units) for a replica whose
#: tenant is burning its SLO budget or holding an active drift alarm —
#: large enough that a clean replica always wins, small enough that a
#: fully-degraded fleet still routes somewhere.
_PENALTY = 1_000_000


def _json_response(status: int, payload, headers: Optional[dict] = None
                   ) -> HTTPResponseData:
    h = {"Content-Type": "application/json"}
    if headers:
        h.update(headers)
    return HTTPResponseData(
        statusCode=status, headers=h,
        entity=json.dumps(payload, default=str).encode(),
    )


class ReplicaHandle:
    """Router-side state for one replica (spawned or attached)."""

    def __init__(self, url: str, models: Sequence[str],
                 proc: Optional[subprocess.Popen] = None,
                 replica_id: str = ""):
        self.url = url.rstrip("/")
        self.models = set(models)
        self.proc = proc
        self.replica_id = replica_id
        self.inflight = 0
        self.healthy = True
        self.draining = False
        self.fail_streak = 0
        # tenant -> {"burning": bool, "drifting": bool} from /driftz
        self.route_health: Dict[str, dict] = {}
        self.lock = threading.Lock()

    def describe(self) -> dict:
        with self.lock:
            return {
                "url": self.url,
                "replica_id": self.replica_id,
                "models": sorted(self.models),
                "inflight": self.inflight,
                "healthy": self.healthy,
                "draining": self.draining,
                "fail_streak": self.fail_streak,
                "route_health": {k: dict(v)
                                 for k, v in self.route_health.items()},
                "pid": self.proc.pid if self.proc is not None else None,
            }


class FleetRouter:
    """Front process fanning requests across replica ServingApps."""

    def __init__(
        self,
        host: str = "127.0.0.1",
        port: int = 0,
        max_inflight: int = 1024,
        health_interval_s: float = 1.0,
        unhealthy_after: int = 3,
        shed_when_all_burning: bool = False,
    ):
        self.admission = AdmissionController(max_inflight=max_inflight)
        self.replicas: List[ReplicaHandle] = []
        self._lock = threading.Lock()
        self._health_interval_s = float(health_interval_s)
        self._unhealthy_after = int(unhealthy_after)
        self._shed_when_all_burning = bool(shed_when_all_burning)
        self._stop = threading.Event()
        self._health_thread: Optional[threading.Thread] = None
        self._started = False
        self._server = HTTPServer(host, port)
        self._server.intake = self._intake

    # -- properties ------------------------------------------------------
    @property
    def url(self) -> str:
        return f"http://{self._server.host}:{self._server.port}"

    @property
    def port(self) -> int:
        return self._server.port

    # -- fleet membership ------------------------------------------------
    def spawn_replica(
        self,
        models: Sequence[Tuple[str, str]],  # [(name, path), ...]
        group: bool = True,
        leaf_dtype: str = "f32",
        extra_env: Optional[dict] = None,
        ready_timeout_s: float = 300.0,
    ) -> ReplicaHandle:
        """Fork one warm-from-disk replica process and wait for ready.

        The child gets ``MMLSPARK_TPU_REPLICA_ID=r<i>`` so its obs
        export/blackbox files are namespaced per replica (obs/_state.py)
        — N same-host replicas never clobber one another's telemetry.

        The router process must not hold the accelerator itself (one
        process per chip): a caller that trained its models in this
        process on a TPU is refused — see serve/README.md.
        """
        from mmlspark_tpu.core.env import refuse_child_on_held_chip

        refuse_child_on_held_chip("FleetRouter.spawn_replica")
        with self._lock:
            replica_id = f"r{len(self.replicas)}"
        cmd = [sys.executable, "-m", "mmlspark_tpu.serve.replica",
               "--port", "0", "--replica-id", replica_id]
        for name, path in models:
            cmd += ["--model", f"{name}={path}"]
        if group and len(models) > 1:
            cmd += ["--group", "--leaf-dtype", leaf_dtype]
        env = dict(os.environ)
        env["MMLSPARK_TPU_REPLICA_ID"] = replica_id
        if extra_env:
            env.update(extra_env)
        with obs.span("router.spawn_replica", replica=replica_id):
            proc = subprocess.Popen(
                cmd, cwd=_REPO_ROOT, env=env,
                stdout=subprocess.PIPE, stderr=subprocess.DEVNULL, text=True,
            )
            try:
                ready = self._await_ready_line(proc, ready_timeout_s)
            except Exception:
                proc.terminate()
                try:
                    proc.wait(timeout=10)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait()
                raise
        handle = ReplicaHandle(
            ready["url"], [name for name, _ in models], proc=proc,
            replica_id=replica_id,
        )
        self._register(handle)
        return handle

    @staticmethod
    def _await_ready_line(proc: subprocess.Popen, timeout_s: float) -> dict:
        """The replica prints one JSON line once /readyz would be 200."""
        deadline = time.monotonic() + timeout_s
        line = ""
        while time.monotonic() < deadline:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"replica exited with {proc.returncode} before ready"
                )
            line = proc.stdout.readline()
            if line.strip():
                break
        if not line.strip():
            raise TimeoutError(f"replica not ready after {timeout_s}s")
        return json.loads(line)

    def attach_replica(self, url: str,
                       models: Optional[Sequence[str]] = None
                       ) -> ReplicaHandle:
        """Adopt an already-running replica (in-process ServingApp in
        tests, externally-managed process in prod).  The router never
        owns its lifecycle — ``stop()`` leaves attached replicas alone."""
        if models is None:
            with urllib.request.urlopen(url.rstrip("/") + "/readyz",
                                        timeout=10) as r:
                body = json.loads(r.read().decode())
            models = sorted((body.get("models") or {}).keys())
        handle = ReplicaHandle(url, models)
        self._register(handle)
        return handle

    def _register(self, handle: ReplicaHandle) -> None:
        with self._lock:
            self.replicas.append(handle)
        for name in handle.models:
            self.admission.register_route(name)
        obs.inc("router.replicas_added")

    # -- lifecycle -------------------------------------------------------
    def start(self) -> "FleetRouter":
        if self._started:
            return self
        if not obs.enabled():
            obs.enable()
        self._server.start()
        self._started = True
        self._stop.clear()
        self._health_thread = threading.Thread(
            target=self._health_loop, daemon=True, name="router-health"
        )
        self._health_thread.start()
        self.admission.set_ready(True)
        obs.inc("router.starts")
        return self

    def stop(self, drain_s: float = 10.0, kill_timeout_s: float = 15.0
             ) -> bool:
        """Drain the front, then drain-or-kill every SPAWNED replica:
        SIGTERM triggers the replica's graceful stop (admission drain +
        worker join); SIGKILL only fires if that exceeds the timeout."""
        drained = self.admission.begin_drain(timeout_s=drain_s)
        self._stop.set()
        if self._health_thread is not None:
            self._health_thread.join(timeout=5.0)
        self._server.stop()
        self.admission.set_ready(False)
        with self._lock:
            handles = list(self.replicas)
        for h in handles:
            if h.proc is None or h.proc.poll() is not None:
                continue
            h.proc.terminate()  # SIGTERM → replica's graceful stop()
            try:
                h.proc.wait(timeout=kill_timeout_s)
            except subprocess.TimeoutExpired:
                h.proc.kill()
                h.proc.wait()
                obs.inc("router.replica_kills")
        obs.inc("router.stops", clean=drained)
        return drained

    # -- health + SLO/drift polling --------------------------------------
    def _health_loop(self) -> None:
        while not self._stop.wait(self._health_interval_s):
            with self._lock:
                handles = list(self.replicas)
            for h in handles:
                self._poll_replica(h)

    def _poll_replica(self, h: ReplicaHandle) -> None:
        try:
            with urllib.request.urlopen(h.url + "/readyz", timeout=5) as r:
                ready = r.status == 200
            route_health = self._read_driftz(h)
        except (urllib.error.URLError, OSError, ValueError):
            with h.lock:
                h.fail_streak += 1
                if h.fail_streak >= self._unhealthy_after:
                    if h.healthy:
                        obs.inc("router.replica_unhealthy",
                                replica=h.replica_id)
                    h.healthy = False
            return
        with h.lock:
            h.fail_streak = 0
            h.healthy = ready
            h.route_health = route_health

    def _read_driftz(self, h: ReplicaHandle) -> Dict[str, dict]:
        """Per-tenant reroute signals from the replica's /driftz payload:
        ``burning`` = the obs SLO evaluator's multiwindow alert on either
        availability or latency budget; ``drifting`` = any active
        feature/score drift alarm."""
        try:
            with urllib.request.urlopen(h.url + "/driftz", timeout=5) as r:
                body = json.loads(r.read().decode())
        except (urllib.error.URLError, OSError, ValueError):
            return {}
        out: Dict[str, dict] = {}
        for name, route in (body.get("routes") or {}).items():
            alerts = (route.get("slo") or {}).get("alerts") or {}
            burning = bool(alerts.get("availability") or alerts.get("latency"))
            drifting = bool(route.get("alarms_active"))
            out[name] = {"burning": burning, "drifting": drifting}
            if burning:
                obs.inc("router.tenant_burning", replica=h.replica_id,
                        model=name)
            if drifting:
                obs.inc("router.tenant_drifting", replica=h.replica_id,
                        model=name)
        return out

    # -- placement -------------------------------------------------------
    def _candidates(self, model: str) -> List[ReplicaHandle]:
        with self._lock:
            handles = list(self.replicas)
        return [
            h for h in handles
            if model in h.models and h.healthy and not h.draining
        ]

    def _pick(self, model: str, exclude=()) -> Optional[ReplicaHandle]:
        best, best_load = None, None
        for h in self._candidates(model):
            if h in exclude:
                continue
            with h.lock:
                load = h.inflight
                rh = h.route_health.get(model, {})
            if rh.get("burning") or rh.get("drifting"):
                load += _PENALTY
            if best_load is None or load < best_load:
                best, best_load = h, load
        return best

    def _all_burning(self, model: str) -> bool:
        cands = self._candidates(model)
        if not cands:
            return False
        for h in cands:
            with h.lock:
                rh = h.route_health.get(model, {})
            if not rh.get("burning"):
                return False
        return True

    # -- transport intake ------------------------------------------------
    def _intake(self, rid: str, req: HTTPRequestData, wait_s: float
                ) -> Optional[HTTPResponseData]:
        path = req.url.split("?", 1)[0]
        if req.method == "GET":
            if path == "/healthz":
                return _json_response(200, {"status": "ok"})
            if path == "/readyz":
                ok = self.admission.ready and bool(
                    [h for h in self.replicas if h.healthy]
                )
                return _json_response(
                    200 if ok else 503, self._fleet_state()
                )
            if path == "/fleetz":
                return _json_response(200, self._fleet_state())
            if path == "/metrics":
                return _json_response(200, obs.snapshot())
            return _json_response(404, {"error": f"no such path: {path}"})
        if req.method != "POST":
            return _json_response(405, {"error": f"method {req.method}"})
        if path == "/admin/swap":
            return self._rolling_swap(req)
        if path.startswith("/models/") and path.endswith("/predict"):
            name = path[len("/models/"):-len("/predict")]
            return self._proxy_predict(name, rid, req, wait_s)
        return _json_response(404, {"error": f"no such path: {path}"})

    def _fleet_state(self) -> dict:
        with self._lock:
            handles = list(self.replicas)
        models = sorted({m for h in handles for m in h.models})
        return {
            "replicas": [h.describe() for h in handles],
            "models": models,
            "inflight": self.admission.inflight(),
            "draining": self.admission.draining,
        }

    def _proxy_predict(self, name: str, rid: str, req: HTTPRequestData,
                       wait_s: float) -> HTTPResponseData:
        if not self._candidates(name):
            # unknown tenant vs temporarily-unplaceable tenant
            with self._lock:
                known = any(name in h.models for h in self.replicas)
            status = 503 if known else 404
            return _json_response(
                status, {"error": f"no replica for model: {name}"}
            )
        # the replicas' own admission machinery, reused at the front:
        # per-tenant concurrency caps + the draining/not_ready gates
        verdict = self.admission.admit_inline(name)
        if verdict is not None:
            return verdict
        try:
            if self._shed_when_all_burning and self._all_burning(name):
                obs.inc("router.shed_burning", model=name)
                return _json_response(
                    429, {"error": "all replicas burning SLO budget"},
                    {"Retry-After": "1"},
                )
            return self._forward(name, req, wait_s)
        finally:
            self.admission.complete(name)

    def _forward(self, name: str, req: HTTPRequestData, wait_s: float
                 ) -> HTTPResponseData:
        tried: List[ReplicaHandle] = []
        last_err = "no healthy replica"
        # one retry on a DIFFERENT replica: transport errors only (a
        # replica's HTTP status, even 5xx, is authoritative — retrying
        # a failed predict elsewhere would double-charge admission)
        for _ in range(2):
            h = self._pick(name, exclude=tried)
            if h is None:
                break
            tried.append(h)
            with h.lock:
                h.inflight += 1
            t0 = time.monotonic()
            try:
                resp = self._do_request(h, req, wait_s)
                obs.observe("router.proxy_s", time.monotonic() - t0)
                obs.inc("router.requests", model=name,
                        replica=h.replica_id, status=resp.statusCode)
                return resp
            except (urllib.error.URLError, OSError, TimeoutError) as e:
                last_err = repr(e)
                with h.lock:
                    h.fail_streak += 1
                    if h.fail_streak >= self._unhealthy_after:
                        h.healthy = False
                obs.inc("router.proxy_errors", replica=h.replica_id)
            finally:
                with h.lock:
                    h.inflight -= 1
        obs.inc("router.unrouted", model=name)
        return _json_response(
            503, {"error": f"fleet unavailable for {name}: {last_err}"}
        )

    def _do_request(self, h: ReplicaHandle, req: HTTPRequestData,
                    wait_s: float) -> HTTPResponseData:
        path = req.url if req.url.startswith("/") else "/" + req.url
        headers = {"Content-Type": "application/json"}
        for k, v in (req.headers or {}).items():
            if k.lower() in ("x-request-id", "x-request-deadline-ms"):
                headers[k] = v
        r = urllib.request.Request(
            h.url + path, data=req.entity or b"", headers=headers,
            method="POST",
        )
        try:
            with urllib.request.urlopen(r, timeout=wait_s + 5.0) as resp:
                return self._to_response(resp.status, resp.headers,
                                         resp.read())
        except urllib.error.HTTPError as e:
            # replica answered: its status (429/503/5xx) is the answer
            return self._to_response(e.code, e.headers, e.read())

    @staticmethod
    def _to_response(status: int, headers, body: bytes) -> HTTPResponseData:
        keep = {}
        for k in ("Content-Type", "X-Model-Version", "X-Request-Id",
                  "Retry-After"):
            v = headers.get(k) if headers is not None else None
            if v:
                keep[k] = v
        return HTTPResponseData(statusCode=int(status), headers=keep,
                                entity=body)

    # -- rolling hot swap ------------------------------------------------
    def _rolling_swap(self, req: HTTPRequestData) -> HTTPResponseData:
        """Swap one tenant across the fleet, one replica at a time.  The
        draining mark steers NEW traffic off the replica mid-swap (its
        own flip→drain keeps in-flight requests safe), so the fleet
        never has two replicas swapping at once and other tenants keep
        every replica in rotation."""
        try:
            payload = json.loads((req.entity or b"").decode() or "{}")
        except (ValueError, UnicodeDecodeError) as e:
            return _json_response(400, {"error": f"bad JSON: {e}"})
        name, path = payload.get("model"), payload.get("path")
        if not name or not path:
            return _json_response(
                400, {"error": 'body needs "model" and "path"'}
            )
        with self._lock:
            targets = [h for h in self.replicas if name in h.models]
        if not targets:
            return _json_response(404, {"error": f"no such model: {name}"})
        results = []
        status = 200
        for h in targets:
            with h.lock:
                h.draining = True
            try:
                with obs.span("router.swap", model=name,
                              replica=h.replica_id):
                    r = urllib.request.Request(
                        h.url + "/admin/swap",
                        data=json.dumps(
                            {"model": name, "path": path}
                        ).encode(),
                        headers={"Content-Type": "application/json"},
                        method="POST",
                    )
                    try:
                        with urllib.request.urlopen(r, timeout=600) as resp:
                            results.append({
                                "replica": h.replica_id,
                                "status": resp.status,
                                **json.loads(resp.read().decode() or "{}"),
                            })
                    except urllib.error.HTTPError as e:
                        status = 500
                        results.append({
                            "replica": h.replica_id, "status": e.code,
                            "error": e.read().decode()[:500],
                        })
                    except (urllib.error.URLError, OSError) as e:
                        status = 500
                        results.append({
                            "replica": h.replica_id, "error": repr(e),
                        })
            finally:
                with h.lock:
                    h.draining = False
        obs.inc("router.rolling_swaps", model=name, clean=status == 200)
        return _json_response(status, {"model": name, "replicas": results})
