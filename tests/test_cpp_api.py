"""C++ public API (N15): msgpack wire + function-descriptor tasks.

Reference analog: ``cpp/include/ray/api.h`` usage tests — a C++ binary
submits work to a running cluster and reads results. Also unit-tests the
Python side of the cross-language codec (``runtime/xlang.py``) and the
msgpack RPC frames the C++ client speaks.
"""

import os
import socket
import struct
import subprocess

import pytest

import ray_tpu
from ray_tpu.runtime import xlang

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLE = os.path.join(REPO, "src", "capi", "example_submit")


def test_xlang_codec_roundtrip():
    cases = [
        None, True, False, 0, 1, 127, 128, -1, -32, -33, 2**40, -(2**40),
        3.5, -0.25, "", "hello", "ünïcode", b"", b"\x00\xffbin",
        [], [1, "two", None, [3.0]], {},
        {"k": 1, "nested": {"a": [True, {"b": b"x"}]}},
    ]
    for case in cases:
        out = xlang.loads(xlang.dumps(case))
        if isinstance(case, tuple):
            case = list(case)
        assert out == case, (case, out)


def test_xlang_codec_rejects_objects():
    with pytest.raises(TypeError):
        xlang.dumps(object())
    with pytest.raises(TypeError):
        xlang.dumps({"fn": lambda: 1})


def test_function_ref_resolution():
    fn = xlang.resolve_function_ref("ray_tpu.examples.xlang:add")
    assert fn(2, 3) == 5
    with pytest.raises(ValueError):
        xlang.resolve_function_ref("no_colon_here")


def _msgpack_call(addr, method, **params):
    """Speak the C++ client's wire from Python: framed 'M'+msgpack."""
    params["method"] = method
    params["_id"] = 0
    payload = b"M" + xlang.dumps(params)
    with socket.create_connection(tuple(addr), timeout=30) as s:
        s.sendall(struct.pack(">Q", len(payload)) + payload)
        hdr = b""
        while len(hdr) < 8:
            hdr += s.recv(8 - len(hdr))
        (n,) = struct.unpack(">Q", hdr)
        buf = b""
        while len(buf) < n:
            buf += s.recv(min(1 << 20, n - len(buf)))
    assert buf[:1] == b"M", "server must answer msgpack with msgpack"
    reply = xlang.loads(buf[1:])
    if reply.get("error") is not None:
        raise RuntimeError(reply["error"])
    return reply["result"]


@pytest.fixture
def cluster():
    from ray_tpu.cluster_utils import Cluster

    ray_tpu.shutdown()
    c = Cluster()
    c.add_node(num_cpus=2)
    yield c
    c.shutdown()


def test_msgpack_wire_against_gcs(cluster):
    nodes = _msgpack_call(cluster.gcs_address, "get_nodes", alive_only=True)
    assert len(nodes) == 1
    assert nodes[0]["resources"]["CPU"] == 2.0


def test_msgpack_xlang_put_get_and_task(cluster):
    raylet_addr = next(iter(cluster.nodes.values())).address
    r = _msgpack_call(raylet_addr, "xlang_put",
                      value={"x": 7, "l": [1, 2]})
    oid = r["oid"]
    got = _msgpack_call(raylet_addr, "xlang_get", oid=oid, timeout_s=5.0)
    assert got["value"] == {"x": 7, "l": [1, 2]}
    # descriptor task executed by a Python worker
    import ray_tpu.utils.ids as ids

    rid = ids.ObjectID.from_random().hex()
    _msgpack_call(raylet_addr, "submit_task", task={
        "task_id": ids.TaskID.from_random().hex(),
        "name": "xlang-add",
        "function_ref": "ray_tpu.examples.xlang:add",
        "args": [20, 22],
        "return_oids": [rid],
        "resources": {"CPU": 1.0},
        "strategy": {"kind": "DEFAULT"},
        "max_retries": 0,
    })
    got = _msgpack_call(raylet_addr, "xlang_get", oid=rid, timeout_s=30.0)
    assert got["value"] == 42


def test_cpp_example_binary(cluster):
    # the cluster has opened the shm store by now, and with it the one
    # native build has run: the example binary is part of it
    host, port = cluster.gcs_address
    proc = subprocess.run([EXAMPLE, host, str(port)], capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode == 0, (proc.stdout, proc.stderr)
    assert '"task": "ok"' in proc.stdout
    assert '"stats": "ok"' in proc.stdout
