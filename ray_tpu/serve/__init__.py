"""ray_tpu.serve — model serving with a reconciling control plane.

Equivalent of Ray Serve (ref: python/ray/serve/): a detached controller
actor reconciles target vs running replicas (health checks, rolling
updates, request-based autoscaling), DeploymentHandles route with
power-of-two-choices, an HTTP proxy serves JSON ingress, and
MeshDeployment hosts pjit-sharded models on gangs of mesh workers.

    @serve.deployment(num_replicas=2)
    class Model:
        def __call__(self, request): ...

    handle = serve.run(Model.bind(arg))
    result = ray_tpu.get(handle.remote(payload))
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional

import cloudpickle

import ray_tpu

from .batching import batch  # noqa: F401 — serve.batch decorator
from .config import AutoscalingConfig, DeploymentConfig
from .controller import CONTROLLER_NAME, get_or_create_controller
from .handle import DeploymentHandle
from .mesh_replica import MeshDeployment
from .multiplex import (get_multiplexed_model_id,  # noqa: F401
                        multiplexed)

__all__ = [
    "AutoscalingConfig", "Application", "Deployment", "DeploymentHandle",
    "MeshDeployment", "delete", "deployment", "get_deployment_handle",
    "get_multiplexed_model_id", "llm", "multiplexed", "run", "shutdown",
    "start_grpc_proxy", "start_http_proxy", "status",
]


def __getattr__(name):
    # serve.llm pulls in jax + the model zoo; load it lazily so plain
    # serve users (and the controller actor) never pay that import
    if name == "llm":
        import importlib

        return importlib.import_module(".llm", __name__)
    raise AttributeError(name)


@dataclass
class Application:
    """A bound deployment (ref: serve/api.py Application / DAG node).
    Nested Applications in args are deployed first and replaced with
    handles — model composition."""
    deployment: "Deployment"
    args: tuple = ()
    kwargs: Dict[str, Any] = field(default_factory=dict)


class Deployment:
    def __init__(self, target: Any, name: str, config: DeploymentConfig):
        self._target = target
        self.name = name
        self.config = config

    def options(self, *, name: Optional[str] = None, **kw) -> "Deployment":
        cfg = DeploymentConfig(**{**self.config.__dict__, **kw})
        return Deployment(self._target, name or self.name, cfg)

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)

    def __repr__(self):
        return f"Deployment({self.name!r})"


def deployment(target: Any = None, *, name: Optional[str] = None,
               num_replicas: int = 1, max_concurrent_queries: int = 8,
               health_check_period_s: float = 2.0,
               health_check_timeout_s: float = 10.0,
               user_config: Any = None,
               ray_actor_options: Optional[dict] = None,
               autoscaling_config: Optional[AutoscalingConfig] = None,
               slo_target_s: Optional[float] = None):
    """@serve.deployment — class or function (ref: serve/api.py:deployment).

    ``slo_target_s`` sets the deployment's end-to-end latency SLO:
    routed requests count into
    ``ray_tpu_serve_slo_{ok,violated}_total{deployment=...}``."""

    def wrap(t):
        cfg = DeploymentConfig(
            num_replicas=num_replicas,
            max_concurrent_queries=max_concurrent_queries,
            health_check_period_s=health_check_period_s,
            health_check_timeout_s=health_check_timeout_s,
            user_config=user_config,
            ray_actor_options=dict(ray_actor_options or {}),
            autoscaling=autoscaling_config,
            slo_target_s=slo_target_s,
        )
        return Deployment(t, name or t.__name__, cfg)

    return wrap(target) if target is not None else wrap


def _deploy_app(controller, app: Application) -> str:
    # depth-first: nested Applications become handles (model composition)
    def resolve(v):
        if isinstance(v, Application):
            _deploy_app(controller, v)
            return DeploymentHandle(v.deployment.name)
        return v

    args = tuple(resolve(a) for a in app.args)
    kwargs = {k: resolve(v) for k, v in app.kwargs.items()}
    d = app.deployment
    blob = cloudpickle.dumps(d._target)
    ray_tpu.get(controller.deploy.remote(d.name, blob, args, kwargs,
                                         d.config), timeout=60)
    return d.name


def run(app: Application, *, wait_for_healthy: bool = True,
        timeout: float = 120.0) -> DeploymentHandle:
    """Deploy the application graph; returns the root handle
    (ref: serve/api.py:414 serve.run)."""
    controller = get_or_create_controller()
    root = _deploy_app(controller, app)
    if wait_for_healthy:
        _wait_healthy(controller, root, timeout)
    return DeploymentHandle(root)


def _wait_healthy(controller, name: str, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        st = ray_tpu.get(controller.status.remote(), timeout=30).get(name)
        if st and st["status"] == "HEALTHY":
            return
        if st and st.get("constructor_error"):
            raise RuntimeError(
                f"deployment {name}: a replica's constructor raised\n"
                f"{st['constructor_error']}")
        time.sleep(0.1)
    raise TimeoutError(f"deployment {name} not healthy after {timeout}s")


def get_deployment_handle(name: str) -> DeploymentHandle:
    return DeploymentHandle(name)


def status() -> Dict[str, dict]:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    return ray_tpu.get(controller.status.remote(), timeout=30)


def delete(name: str) -> None:
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    ray_tpu.get(controller.delete.remote(name), timeout=60)


def start_grpc_proxy(host: str = "127.0.0.1", port: int = 0) -> tuple:
    """Start the gRPC ingress actor (ref: serve gRPC proxy path);
    returns (host, port). Generic-handler service — see
    serve/grpc_proxy.py for the wire contract."""
    from .grpc_proxy import GrpcProxy

    cls = ray_tpu.remote(GrpcProxy)
    proxy = cls.options(name="SERVE_GRPC_PROXY", lifetime="detached",
                        get_if_exists=True).remote(host, port)
    return tuple(ray_tpu.get(proxy.address.remote(), timeout=30))


def start_http_proxy(host: str = "127.0.0.1", port: int = 0,
                     asyncio_server: bool = True) -> tuple:
    """Start the HTTP ingress actor; returns (host, port). The default is
    the asyncio proxy (http_asyncio.py — the reference's uvicorn/ASGI
    analog); asyncio_server=False keeps the stdlib thread-per-request
    fallback."""
    if asyncio_server:
        from .http_asyncio import AsyncHTTPProxy as ProxyCls
    else:
        from .http_proxy import HTTPProxy as ProxyCls

    cls = ray_tpu.remote(ProxyCls)
    proxy = cls.options(name="SERVE_PROXY", lifetime="detached",
                        get_if_exists=True).remote(host, port)
    return tuple(ray_tpu.get(proxy.address.remote(), timeout=30))


def deploy_config(path: str) -> dict:
    """`serve deploy <config>`: declarative YAML/JSON application config
    (ref: python/ray/serve/schema.py ServeDeploySchema + `serve deploy`).

    Schema:
        http: {host: ..., port: ...}            # optional ingress
        applications:
          - name: my_app                        # optional
            import_path: pkg.module:app         # Application or builder
            args: {...}                         # builder kwargs
            num_replicas: 2                     # per-deployment override

    Returns {"deployments": [names], "http": (host, port) | None}."""
    import importlib

    import yaml

    with open(path) as f:
        cfg = yaml.safe_load(f)
    apps = cfg.get("applications") or []
    if not apps:
        raise ValueError(f"{path}: no applications in config")
    deployed = []
    for app_cfg in apps:
        import_path = app_cfg["import_path"]
        mod_name, _, attr = import_path.partition(":")
        if not attr:
            raise ValueError(
                f"import_path must be 'module:attr', got {import_path!r}")
        target = getattr(importlib.import_module(mod_name), attr)
        if callable(target) and not isinstance(target, Application):
            target = target(**(app_cfg.get("args") or {}))
        if not isinstance(target, Application):
            raise TypeError(f"{import_path} is not a serve Application")
        if app_cfg.get("num_replicas"):
            target.deployment.config.num_replicas = int(
                app_cfg["num_replicas"])
        deployed.append(run(target))
    http = cfg.get("http")
    addr = None
    if http is not None:
        addr = start_http_proxy(http.get("host", "127.0.0.1"),
                                int(http.get("port", 8000)))
    return {"deployments": [d._name for d in deployed], "http": addr}


def shutdown() -> None:
    """Tear down every deployment and the controller."""
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME)
    except Exception:
        return
    try:
        ray_tpu.get(controller.shutdown.remote(), timeout=30)
    except Exception:
        pass
    try:
        ray_tpu.kill(controller)
    except Exception:
        pass
    for proxy_name in ("SERVE_PROXY", "SERVE_GRPC_PROXY"):
        try:
            proxy = ray_tpu.get_actor(proxy_name)
            ray_tpu.get(proxy.shutdown.remote(), timeout=10)
            ray_tpu.kill(proxy)
        except Exception:
            pass
