"""Serve public API (reference: python/ray/serve/api.py — serve.start,
@serve.deployment, serve.run, serve.delete, serve.status, serve.shutdown,
deployment .bind() graphs, get_deployment_handle)."""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional

import ray_tpu
from ray_tpu.util import spans, tracing
from ray_tpu.serve._private import (
    CONTROLLER_NAME, SERVE_NAMESPACE, AutoscalingConfig, DeploymentConfig,
    DeploymentHandle, ServeController)

_http_proxy = None


def _get_or_start_controller():
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
    except ValueError:
        # (once a session: in the start-up record, until it answers)
        with spans.span("serve", "controller_start", pin=True):
            controller = ServeController.options(
                name=CONTROLLER_NAME, namespace=SERVE_NAMESPACE,
                lifetime="detached", num_cpus=0.1,
                get_if_exists=True).remote()
            ray_tpu.get(controller.list_deployments.remote(), timeout=120)
        # Fire-and-forget: the autoscaling/reconciliation loop runs on one
        # of the threaded controller's pool threads (idempotent), for the
        # controller's life: in no trace, whoever's call started it.
        with tracing.untraced():
            controller.run_control_loop.remote()
    return controller


def start(http_host: str = "127.0.0.1", http_port: int = 0,
          with_proxy: bool = False) -> Optional[int]:
    """Start the Serve control plane (+ optionally the HTTP ingress).
    Returns the proxy port when a proxy was started."""
    global _http_proxy
    _get_or_start_controller()
    if with_proxy:
        if _http_proxy is None:
            from ray_tpu.serve._proxy import HTTPProxyActor
            _http_proxy = HTTPProxyActor.options(num_cpus=0.1).remote(
                http_host, http_port)
        return ray_tpu.get(_http_proxy.address.remote(), timeout=60)
    return None


class Application:
    """A bound deployment (graph node) ready for serve.run
    (reference: serve/dag.py + deployment .bind())."""

    def __init__(self, deployment: "Deployment", args, kwargs):
        self.deployment = deployment
        self.args = args
        self.kwargs = kwargs


class Deployment:
    """Reference: serve/deployment.py — the @serve.deployment object."""

    def __init__(self, cls_or_fn, name: str, config: DeploymentConfig):
        self._cls_or_fn = cls_or_fn
        self.name = name
        self._config = config

    def options(self, *, name: Optional[str] = None,
                num_replicas: Optional[int] = None,
                max_concurrent_queries: Optional[int] = None,
                ray_actor_options: Optional[dict] = None,
                user_config: Any = None,
                autoscaling_config: Optional[dict] = None,
                queue_limit: Optional[int] = None) -> "Deployment":
        import copy
        cfg = copy.deepcopy(self._config)
        if num_replicas is not None:
            cfg.num_replicas = num_replicas
        if max_concurrent_queries is not None:
            cfg.max_concurrent_queries = max_concurrent_queries
        if queue_limit is not None:
            cfg.queue_limit = queue_limit
        if ray_actor_options is not None:
            cfg.ray_actor_options = dict(ray_actor_options)
        if user_config is not None:
            cfg.user_config = user_config
        if autoscaling_config is not None:
            cfg.autoscaling_config = (
                autoscaling_config
                if isinstance(autoscaling_config, AutoscalingConfig)
                else AutoscalingConfig(**autoscaling_config))
        new_name = name or self.name
        cfg.name = new_name
        return Deployment(self._cls_or_fn, new_name, cfg)

    def bind(self, *args, **kwargs) -> Application:
        return Application(self, args, kwargs)


def deployment(_cls_or_fn=None, *, name: Optional[str] = None,
               num_replicas: int = 1, max_concurrent_queries: int = 100,
               ray_actor_options: Optional[dict] = None,
               user_config: Any = None,
               autoscaling_config: Optional[dict] = None,
               queue_limit: Optional[int] = None):
    """@serve.deployment decorator.

    `queue_limit` bounds how many requests may WAIT for a replica slot
    (per deployment, per client process) before the router sheds new
    arrivals with ServeOverloadedError; None uses the
    ``serve_queue_length`` config default, 0 disables shedding."""

    def wrap(cls_or_fn):
        dep_name = name or getattr(cls_or_fn, "__name__", "deployment")
        auto = None
        if autoscaling_config is not None:
            auto = (autoscaling_config
                    if isinstance(autoscaling_config, AutoscalingConfig)
                    else AutoscalingConfig(**autoscaling_config))
        cfg = DeploymentConfig(
            name=dep_name, num_replicas=num_replicas,
            max_concurrent_queries=max_concurrent_queries,
            ray_actor_options=dict(ray_actor_options or {}),
            user_config=user_config,
            autoscaling_config=auto,
            queue_limit=queue_limit)
        return Deployment(cls_or_fn, dep_name, cfg)

    return wrap(_cls_or_fn) if _cls_or_fn is not None else wrap


def run(target: Application, *, _blocking: bool = False) -> DeploymentHandle:
    """Deploy an application graph; returns the ingress handle
    (reference: serve/api.py serve.run).  Bound arguments that are
    themselves Applications deploy first and are passed as handles —
    the deployment-graph composition path.

    One `serve/run` span in the start-up record, with the controller's
    start and each `serve/deploy` (the call that returns when the
    deployment's replicas are RUNNING) as children; what the controller
    and the replicas do meanwhile hangs off these in their own records."""
    def deploy_app(app: Application) -> DeploymentHandle:
        resolved_args = tuple(
            deploy_app(a) if isinstance(a, Application) else a
            for a in app.args)
        resolved_kwargs = {
            k: deploy_app(v) if isinstance(v, Application) else v
            for k, v in app.kwargs.items()}
        dep = app.deployment
        with spans.span("serve", "deploy", pin=True, name=dep.name):
            ray_tpu.get(controller.deploy.remote(
                dep._config, dep._cls_or_fn, resolved_args, resolved_kwargs),
                timeout=300)
        return DeploymentHandle(dep.name)

    with spans.span("serve", "run", pin=True):
        controller = _get_or_start_controller()
        return deploy_app(target)


def get_deployment_handle(name: str) -> DeploymentHandle:
    return DeploymentHandle(name)


def status() -> dict:
    controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
    return ray_tpu.get(controller.list_deployments.remote(), timeout=30)


def delete(name: str) -> bool:
    controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
    return ray_tpu.get(controller.delete_deployment.remote(name),
                       timeout=60)


def shutdown():
    global _http_proxy
    try:
        controller = ray_tpu.get_actor(CONTROLLER_NAME, SERVE_NAMESPACE)
    except ValueError:
        return
    try:
        ray_tpu.get(controller.shutdown.remote(), timeout=60)
    finally:
        ray_tpu.kill(controller)
        if _http_proxy is not None:
            try:
                ray_tpu.kill(_http_proxy)
            except Exception:
                pass
            _http_proxy = None
