"""Replica context: which deployment/replica the current code runs in.

Reference analog: ``serve.get_replica_context()``
(``serve/context.py`` — ReplicaContext dataclass). The hosting
``_Replica`` actor sets the context on its own thread before
constructing the user deployment object, so engine code (e.g.
``serve/paged_llm.py``) can tag its metrics series and prefix-cache digests
with the deployment name and a stable replica tag. Thread-local: in
local mode several replicas share one process, and each actor
constructs its body on its own thread."""

from __future__ import annotations

import threading
from dataclasses import dataclass


@dataclass(frozen=True)
class ReplicaContext:
    deployment: str
    replica_tag: str


_local = threading.local()


def set_replica_context(deployment: str | None,
                        replica_tag: str | None) -> None:
    """Install (or clear, with Nones) the calling thread's context."""
    if deployment is None or replica_tag is None:
        _local.ctx = None
    else:
        _local.ctx = ReplicaContext(deployment=str(deployment),
                                    replica_tag=str(replica_tag))


def get_replica_context() -> ReplicaContext | None:
    return getattr(_local, "ctx", None)
