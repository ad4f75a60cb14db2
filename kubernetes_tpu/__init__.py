"""kubernetes_tpu — a TPU-native cluster-orchestration framework.

A from-scratch re-design of the capabilities of Kubernetes (~v1.8 vintage,
reference: mgugino-upstream-stage/kubernetes) around a TPU-first compute model:

- Cluster state lives on device as a structure-of-arrays tensor database
  (`kubernetes_tpu.state`), the analog of the scheduler cache
  (reference: plugin/pkg/scheduler/schedulercache/node_info.go:34-74).
- Scheduling predicates and priorities are masked XLA ops over a
  (pending_pods x nodes) batch (`kubernetes_tpu.ops`), replacing the
  goroutine fan-out hot loops (reference:
  plugin/pkg/scheduler/core/generic_scheduler.go:163,285).
- A batched, serial-equivalent assignment solver replaces the one-pod-at-a-
  time `scheduleOne` driver (reference: plugin/pkg/scheduler/scheduler.go:253).
- The node axis shards across a `jax.sharding.Mesh` over ICI
  (`kubernetes_tpu.parallel`), the TPU-native equivalent of
  `workqueue.Parallelize(16, len(nodes), ...)`.
- A thin asyncio host plane provides the API-machinery capabilities:
  an object store with optimistic concurrency + watch streams
  (`kubernetes_tpu.apiserver`), reflector/informer caches and rate-limited
  workqueues (`kubernetes_tpu.client`), and reconcile controllers
  (`kubernetes_tpu.controllers`).
- Integration with an unmodified Go control plane goes through the stock
  scheduler-extender HTTP/JSON hook (`kubernetes_tpu.extender`, reference:
  plugin/pkg/scheduler/core/extender.go:40).
"""

__version__ = "0.1.0"
