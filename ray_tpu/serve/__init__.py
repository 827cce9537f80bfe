"""ray_tpu.serve: model serving on the actor runtime.

Role-equivalent to Ray Serve (reference: python/ray/serve — controller
reconcile loop, replica actors, power-of-two routing, batching, HTTP
ingress, request-based autoscaling), TPU-first: replicas reserve chips via
ray_actor_options and batch requests into jit-compiled inference calls.
"""

from .api import (
    Application,
    Deployment,
    delete,
    deployment,
    get_deployment_handle,
    run,
    shutdown,
    start_http,
    status,
    stop_http,
)
from .batching import batch
from .config import deploy as deploy_config
from .adapter_pool import AdapterNotFoundError, AdapterPool
from .engine import (
    EngineConfig,
    EngineOverloadedError,
    InferenceEngine,
    LLMServer,
    llm_app,
    random_lora,
    register_model,
)
from .grpc_ingress import start_grpc, stop_grpc
from .handle import DeploymentHandle, DeploymentResponse
from .multiplex import (
    get_multiplexed_model_id,
    multiplexed,
    pick_replica_for_model,
)
from .prefix_cache import RadixPrefixCache

__all__ = [
    "deployment", "Deployment", "Application", "run", "delete", "status",
    "shutdown", "get_deployment_handle", "DeploymentHandle",
    "DeploymentResponse", "batch", "start_http", "stop_http",
    "multiplexed", "get_multiplexed_model_id", "pick_replica_for_model",
    "deploy_config", "start_grpc", "stop_grpc",
    "EngineConfig", "EngineOverloadedError", "InferenceEngine",
    "LLMServer", "llm_app", "random_lora", "register_model",
    "AdapterPool", "AdapterNotFoundError", "RadixPrefixCache",
]
