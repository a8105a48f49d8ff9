"""Native C++ RecordIO tests (ref: the reference's dmlc RecordIO tests +
format compatibility between python and native implementations)."""
import os

import numpy as onp
import pytest

from mxnet_tpu import recordio
from mxnet_tpu import native


@pytest.fixture
def rec_file(tmp_path):
    path = str(tmp_path / "data.rec")
    w = recordio.MXRecordIO(path, "w")
    payloads = []
    for i in range(23):
        p = bytes([i]) * (i * 7 % 50 + 1)
        payloads.append(p)
        w.write(p)
    w.close()
    return path, payloads


def test_python_roundtrip(rec_file):
    path, payloads = rec_file
    r = recordio.MXRecordIO(path, "r")
    for p in payloads:
        assert r.read() == p
    assert r.read() is None
    r.close()


def test_indexed_recordio(tmp_path):
    path = str(tmp_path / "data.rec")
    idx_path = str(tmp_path / "data.idx")
    w = recordio.MXIndexedRecordIO(idx_path, path, "w")
    for i in range(10):
        w.write_idx(i, f"record-{i}".encode())
    w.close()
    r = recordio.MXIndexedRecordIO(idx_path, path, "r")
    assert r.read_idx(7) == b"record-7"
    assert r.read_idx(0) == b"record-0"
    r.close()


def test_pack_unpack():
    hdr = recordio.IRHeader(0, 3.0, 7, 0)
    s = recordio.pack(hdr, b"payload")
    hdr2, payload = recordio.unpack(s)
    assert hdr2.label == 3.0
    assert hdr2.id == 7
    assert payload == b"payload"
    # vector label
    hdr3 = recordio.IRHeader(0, onp.array([1.0, 2.0, 3.0]), 1, 0)
    s3 = recordio.pack(hdr3, b"x")
    h3, p3 = recordio.unpack(s3)
    assert h3.label.tolist() == [1.0, 2.0, 3.0]


@pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
def test_native_reader_bitcompat(rec_file):
    path, payloads = rec_file
    r = native.NativeRecordIO(path)
    assert len(r) == len(payloads)
    for i, p in enumerate(payloads):
        assert r.read_idx(i) == p
    r.close()


@pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
def test_native_writer_python_reads(tmp_path):
    path = str(tmp_path / "nat.rec")
    w = native.NativeRecordIOWriter(path)
    for i in range(5):
        w.write(f"native-{i}".encode())
    w.close()
    r = recordio.MXRecordIO(path, "r")
    for i in range(5):
        assert r.read() == f"native-{i}".encode()
    r.close()


@pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
def test_native_batch_server(rec_file):
    path, payloads = rec_file
    srv = native.NativeBatchServer(path, batch_size=8, shuffle=False,
                                   num_workers=2)
    batches = list(iter(srv))
    assert len(batches) == 3  # ceil(23/8) with padding
    assert all(len(b) == 8 for b in batches)
    flat = [p for b in batches for p in b]
    assert flat[:23] == payloads
    # shuffled epoch sees all records
    srv2 = native.NativeBatchServer(path, batch_size=8, shuffle=True,
                                    seed=3, num_workers=3)
    got = sorted(p for b in srv2 for p in b)
    for p in payloads:
        assert p in got
    srv.close()
    srv2.close()


@pytest.mark.skipif(not native.available(), reason="no C++ toolchain")
def test_native_batch_server_hands_batches_over_in_order(rec_file):
    """Six workers on batches of one record: whichever finishes first,
    the batches come in the order of their ranges, epoch after epoch."""
    path, payloads = rec_file
    for _ in range(20):
        srv = native.NativeBatchServer(path, batch_size=1, shuffle=False,
                                       num_workers=6)
        assert [p for b in srv for p in b] == payloads
        srv.reset()
        assert [p for b in srv for p in b] == payloads
        srv.close()
