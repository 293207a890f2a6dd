"""ray_tpu.serve — online serving over actors.

Reference surface: Ray Serve (ray: python/ray/serve/ —
@serve.deployment classes, ServeController managing replica actors,
Router with power-of-two-choices replica scheduling, model composition
via DeploymentHandle, HTTP ingress). Minimum-viable parity: deployments
with N replica actors, least-of-two-queues routing, handle composition
through bind(), replica crash recovery, redeploy/scaling, and a small
JSON HTTP ingress.
"""

from ray_tpu.serve.core import (AdmissionShedError,  # noqa: F401
                                Application, AutoscalingConfig,
                                Deployment, DeploymentHandle, deployment,
                                get_app_handle, get_call_span_fields,
                                get_multiplexed_model_id, multiplexed, run,
                                serving_stats, shutdown, start_grpc,
                                start_http, status)

__all__ = [
    "deployment", "run", "shutdown", "status", "get_app_handle",
    "Deployment", "DeploymentHandle", "Application", "start_http",
    "AutoscalingConfig", "multiplexed", "get_multiplexed_model_id",
    "get_call_span_fields", "start_grpc", "AdmissionShedError",
    "serving_stats",
]
