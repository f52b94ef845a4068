"""ray_tpu.serve — model serving on actor replicas.

Reference parity: python/ray/serve/ (SURVEY.md §2.3): controller actor with
deployment reconciliation, replica actors hosting user callables, handle
router with power-of-two-choices routing, max_concurrent_queries
backpressure, bounded-queue load shedding + failure healing, graceful
replica draining, mid-stream failover, per-request deadlines, HTTP
ingress proxy, deployment-graph composition via .bind(), @serve.batch
dynamic batching.
"""

from ray_tpu.exceptions import (  # noqa: F401
    ReplicaStreamLostError,
    ServeOverloadedError,
)
from ray_tpu.serve.api import (  # noqa: F401
    Application,
    Deployment,
    delete,
    deployment,
    get_deployment_handle,
    run,
    shutdown,
    start,
    status,
)
from ray_tpu.serve.asgi import ingress  # noqa: F401
from ray_tpu.serve.batching import batch  # noqa: F401
from ray_tpu.serve.llm import (  # noqa: F401
    LLMDeployment,
    LLMReplica,
    llm_stream_resume,
)
from ray_tpu.serve.kv_tier import (  # noqa: F401
    DecodeLLMDeployment,
    DisaggLLMHandle,
    KVBlockCodec,
    KVCodecError,
    KVTierCache,
    PrefillLLMDeployment,
    run_disaggregated,
)
from ray_tpu.serve._private import DeploymentHandle  # noqa: F401
