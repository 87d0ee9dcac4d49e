"""RPC framing hardening: restricted deserialization + HMAC transport
auth + snapshot atomicity.

The reference's trust boundary here is msgpack + TLS (nomad/rpc.go);
ours is an allowlisted unpickler (no arbitrary-callable resolution ⇒ no
deserialization RCE) plus optional per-frame HMAC.
"""

import os
import pickle
import socket
import threading

import pytest

from nomad_tpu.rpc import framing
from nomad_tpu.rpc.framing import (
    FramingError,
    recv_frame,
    send_frame,
    set_rpc_secret,
)


@pytest.fixture(autouse=True)
def _no_secret():
    set_rpc_secret(None)
    yield
    set_rpc_secret(None)


def _pair():
    a, b = socket.socketpair()
    return a, b


def _roundtrip(msg):
    a, b = _pair()
    out = {}

    def rx():
        out["msg"] = recv_frame(b)

    t = threading.Thread(target=rx)
    t.start()
    send_frame(a, msg)
    t.join(5)
    a.close()
    b.close()
    return out["msg"]


def test_roundtrip_plain_types():
    msg = {"seq": 1, "method": "Node.register", "args": {"x": [1, 2.5, "s", None, True]}}
    assert _roundtrip(msg) == msg


def test_roundtrip_framework_dataclass():
    from nomad_tpu import mock

    node = mock.node()
    got = _roundtrip({"seq": 2, "args": node})
    assert got["args"].id == node.id


def test_malicious_global_rejected():
    """A crafted frame resolving os.system must be refused before any
    callable executes — the classic pickle RCE."""
    class Evil:
        def __reduce__(self):
            return (os.system, ("true",))

    payload = pickle.dumps({"seq": 3, "args": Evil()})
    a, b = _pair()
    a.sendall(framing._LEN.pack(len(payload) + 1) + bytes([0]) + payload)
    with pytest.raises(FramingError, match="disallowed global"):
        recv_frame(b)
    a.close()
    b.close()


def test_non_dataclass_framework_global_rejected():
    """Even nomad_tpu-module globals that aren't dataclasses/enums (i.e.
    functions, arbitrary classes) must not resolve."""

    class Evil:
        def __reduce__(self):
            import nomad_tpu.state.snapshot as s

            return (s.save_snapshot, (None, "/tmp/x"))

    payload = pickle.dumps({"args": Evil()})
    a, b = _pair()
    a.sendall(framing._LEN.pack(len(payload) + 1) + bytes([0]) + payload)
    with pytest.raises(FramingError, match="disallowed global"):
        recv_frame(b)
    a.close()
    b.close()


def test_hmac_roundtrip_and_reject():
    set_rpc_secret(b"cluster-secret")
    msg = {"seq": 4, "result": "ok"}
    assert _roundtrip(msg) == msg

    # unauthenticated frame rejected when a secret is configured
    payload = pickle.dumps(msg)
    a, b = _pair()
    a.sendall(framing._LEN.pack(len(payload) + 1) + bytes([0]) + payload)
    with pytest.raises(FramingError, match="unauthenticated"):
        recv_frame(b)
    a.close()
    b.close()

    # tampered payload rejected
    import hashlib
    import hmac as hmaclib

    tag = hmaclib.new(b"wrong-secret", payload, hashlib.sha256).digest()
    a, b = _pair()
    a.sendall(
        framing._LEN.pack(len(payload) + 1 + len(tag)) + bytes([1]) + tag + payload
    )
    with pytest.raises(FramingError, match="HMAC mismatch"):
        recv_frame(b)
    a.close()
    b.close()


def test_numpy_payload_roundtrip():
    import numpy as np

    got = _roundtrip({"a": np.arange(4, dtype=np.int32)})
    assert got["a"].tolist() == [0, 1, 2, 3]


def test_snapshot_write_is_atomic(tmp_path):
    """A failed snapshot write must not destroy the previous good one."""
    from nomad_tpu import mock
    from nomad_tpu.state.snapshot import restore_snapshot, save_snapshot
    from nomad_tpu.state.store import StateStore

    store = StateStore()
    store.upsert_node(1, mock.node())
    path = str(tmp_path / "state.snap")
    save_snapshot(store, path)
    good = open(path, "rb").read()

    # a crash mid-write leaves only the tmp file partially written; the
    # final path still holds the previous snapshot
    with open(path + ".tmp", "wb") as f:
        f.write(good[: len(good) // 2])
    assert open(path, "rb").read() == good
    restored = restore_snapshot(path)
    assert len(restored.nodes()) == 1
