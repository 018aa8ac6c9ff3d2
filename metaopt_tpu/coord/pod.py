"""``jax.distributed`` glue: one coordinator per pod, agreed pod-wide.

The reference's multi-node story is "point every worker at the same Mongo
URL" (SURVEY.md §3.2). The pod-native story: the host running JAX process 0
starts the :class:`~metaopt_tpu.coord.server.CoordServer`, and the service
address is agreed across processes with one tiny all-broadcast over the
pod's existing collective channel — no out-of-band config needed. DCN-side
(multi-slice) workers can instead be pointed at ``coord://host:port``
explicitly, exactly like a Mongo URL.
"""

from __future__ import annotations

import logging
import socket
from typing import Optional, Tuple

from metaopt_tpu.coord.server import CoordServer

log = logging.getLogger(__name__)

_ADDR_BYTES = 64  # fixed-size frame for the broadcast: 62B host + 2B port


def _encode_addr(host: str, port: int):
    import numpy as np

    raw = host.encode("utf-8")[: _ADDR_BYTES - 2]
    buf = np.zeros(_ADDR_BYTES, dtype=np.uint8)
    buf[: len(raw)] = np.frombuffer(raw, dtype=np.uint8)
    buf[-2] = port >> 8
    buf[-1] = port & 0xFF
    return buf


def _decode_addr(buf) -> Tuple[str, int]:
    import numpy as np

    arr = np.asarray(buf, dtype=np.uint8)
    host = bytes(arr[:-2]).rstrip(b"\x00").decode("utf-8")
    return host, (int(arr[-2]) << 8) | int(arr[-1])


def _local_host_ip() -> str:
    """The address other pod hosts can reach us on (best effort)."""
    try:
        with socket.socket(socket.AF_INET, socket.SOCK_DGRAM) as s:
            s.connect(("10.255.255.255", 1))  # no packet sent; routes only
            return s.getsockname()[0]
    except OSError:
        return socket.gethostbyname(socket.gethostname())


def start_pod_coordinator(
    snapshot_path: Optional[str] = None,
    stale_timeout_s: Optional[float] = 60.0,
    event_log_path: Optional[str] = None,
    port: int = 0,
) -> Tuple[str, int, Optional[CoordServer]]:
    """Start (on process 0) or discover (elsewhere) the pod's coordinator.

    Returns ``(host, port, server)`` — ``server`` is non-None only on the
    hosting process, which must keep it alive and ``stop()`` it at exit.
    Single-process runs degenerate to a local server, so the same call works
    in tests, on one chip, and on a pod.

    ``jax.process_count()`` initialises the backend: call this only from
    the process that owns this host's chips (a ``jax.distributed`` program
    whose trials run in-process), never from a hunt process that launches
    chip-bound trial subprocesses.
    """
    import jax

    if jax.process_count() == 1:
        server = CoordServer(
            host="127.0.0.1",
            port=port,
            snapshot_path=snapshot_path,
            stale_timeout_s=stale_timeout_s,
            event_log_path=event_log_path,
        ).start()
        h, p = server.address
        return h, p, server

    from jax.experimental import multihost_utils

    server: Optional[CoordServer] = None
    if jax.process_index() == 0:
        host = _local_host_ip()
        server = CoordServer(
            host="0.0.0.0",
            port=port,
            snapshot_path=snapshot_path,
            stale_timeout_s=stale_timeout_s,
            event_log_path=event_log_path,
        ).start()
        addr = _encode_addr(host, server.address[1])
    else:
        addr = _encode_addr("", 0)

    agreed = multihost_utils.broadcast_one_to_all(addr)
    host, p = _decode_addr(agreed)
    log.info(
        "pod coordinator at coord://%s:%d (process %d/%d)",
        host, p, jax.process_index(), jax.process_count(),
    )
    return host, p, server
