"""Serving: the online cluster-serving subsystem over mined results —
snapshot-swapped :class:`TriclusterService` (``serve.service``), ranked
and batched lookups (``serve.ranking``), the cluster-query index with
delta maintenance (``serve.clusters``), the stdlib HTTP
endpoint/client (``serve.protocol``), zero-copy shared-memory snapshot
replicas (``serve.shm``), the sharded query router (``serve.router``),
the fault-tolerance layer — deterministic fault injection
(``serve.faults``) and process supervision (``serve.supervise``).
"""
from .clusters import (ClusterIndex, ClusterView, cluster_query,
                       pack_sig_words)
from .faults import (KILL_EXIT_CODE, DropRequest, Fault, FaultInjector,
                     FaultPlan)
from .protocol import (ClusterClient, ClusterServeServer, health_doc,
                       make_server)
from .ranking import (BatchQuerier, RankingPolicy, cluster_scores,
                      pack_signatures, rank_views, top_clusters,
                      top_from_scores)
from .router import (CircuitBreaker, GatewayTimeout, PooledClient,
                     RouterServer, RouterService, Shard,
                     make_router_server)
from .service import (QueryResult, Snapshot, TriclusterService,
                      snapshot_query, snapshot_query_batch)
from .shm import (ReplicaService, ShmPublisher, ShmReplica,
                  SnapshotBundle, WriterDeadError)
from .supervise import Supervisor, write_restart_flag

__all__ = [
    # cluster-query surface
    "ClusterIndex", "ClusterView", "cluster_query", "pack_sig_words",
    # ranking layer
    "BatchQuerier", "RankingPolicy", "cluster_scores", "pack_signatures",
    "rank_views", "top_clusters", "top_from_scores",
    # snapshot-swapped service + protocol
    "TriclusterService", "Snapshot", "QueryResult",
    "snapshot_query", "snapshot_query_batch",
    "ClusterClient", "ClusterServeServer", "make_server", "health_doc",
    # zero-copy shared-memory replicas
    "ShmPublisher", "ShmReplica", "ReplicaService", "SnapshotBundle",
    "WriterDeadError",
    # sharded query router
    "RouterService", "RouterServer", "Shard", "PooledClient",
    "make_router_server", "CircuitBreaker", "GatewayTimeout",
    # fault tolerance: injection + supervision
    "FaultPlan", "FaultInjector", "Fault", "DropRequest",
    "KILL_EXIT_CODE", "Supervisor", "write_restart_flag",
]
