"""A model blob as PARTS: a small pickled head, then the arrays' own
buffers.

``Algorithm.save_model`` may return a sequence of bytes-like parts
instead of one ``bytes``; ``core/workflow.run_train`` hands them to the
model store in order and ``LocalFSModelStore.put_parts`` writes each
straight from the memory it lies in. So a model whose weight is in its
arrays is never copied, pickled or compressed on its way to the disk:
float32 factors do not compress, and a copy of 2.8 GB costs seconds.

Layout of such a blob (the parts, joined)::

    magic | <Q n | head: n bytes | array 0 | array 1 | ...

``head`` is a pickled dict, zero-padded so that array 0 starts on a
multiple of 64 (``pickle.loads`` ignores what follows the pickle); its
``"leaves"`` lists each array's dtype and shape, in order — and, for
an array that lies in memory with its axes in another order (a
column-major head as a fetch from the TPU returns it), that order: the
array is written as it lies and loaded back with the same strides.
``unpack`` returns ``np.frombuffer`` views of the blob it is given —
read-only, no copy.
"""

from __future__ import annotations

import io
import pickle
import struct
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

#: first bytes of a blob laid out as above (a pickle starts with 0x80)
MAGIC = b"PIOARRAYS1\n"

#: arrays start on a multiple of this, so that views of a blob that
#: ``bytes`` holds are aligned for every dtype
ALIGN = 64


def byte_view(part: Any) -> memoryview:
    """``part`` (bytes, memoryview, a numpy array) as a flat view of
    its bytes: nothing is copied unless the array is not contiguous."""
    if isinstance(part, np.ndarray):
        # through uint8: a dtype the buffer protocol has no code for
        # (bfloat16) exports all the same
        part = np.ascontiguousarray(part).reshape(-1).view(np.uint8)
    return memoryview(part).cast("B")


def _dtype_tag(dtype: np.dtype) -> str:
    """What the head says of a dtype: its ``str`` (byte order and all),
    or its NAME where numpy has no code for it (``bfloat16``, whose
    ``str`` is the void ``<V2``)."""
    return dtype.name if dtype.kind == "V" else dtype.str


def _dtype_of(tag: str) -> np.dtype:
    try:
        return np.dtype(tag)
    except TypeError:       # one of ml_dtypes', not registered yet
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, tag))


def _memory_order(a: np.ndarray) -> Optional[Tuple[int, ...]]:
    """The axes of ``a`` from slowest to fastest AS IT LIES in memory,
    where that is not their own order and ``a.transpose(order)`` is
    C-contiguous: the array can then be written as it lies, with no
    copy. A fetch from the TPU hands back such arrays (a head of
    2048 x 19,360 column-major: the device's layout kept). None where
    ``a`` is C-contiguous already, or no order of its axes is."""
    if a.flags.c_contiguous:
        return None
    order = tuple(int(i) for i in np.argsort(
        [-s for s in a.strides], kind="stable"))
    return order if a.transpose(order).flags.c_contiguous else None


def lead(magic: bytes, head: bytes, fill: bytes) -> bytes:
    """``magic | <Q n | head``, ``head`` lengthened with ``fill`` (``n``
    counts it) so that what follows starts on a multiple of ALIGN."""
    pad = -(len(magic) + 8 + len(head)) % ALIGN
    return b"".join([magic, struct.pack("<Q", len(head) + pad), head,
                     fill * pad])


def split_lead(blob: Any, magic: bytes
               ) -> Optional[Tuple[memoryview, memoryview]]:
    """``(head, what follows it)`` of a blob that starts with a
    :func:`lead` under ``magic``, as views; None where it does not."""
    blob = memoryview(blob)
    at = len(magic) + 8
    if blob[:len(magic)] != magic:
        return None
    (n,) = struct.unpack("<Q", blob[len(magic):at])
    return blob[at:at + n], blob[at + n:]


def pack_arrays(head: Dict[str, Any], arrays: Sequence[np.ndarray],
                magic: bytes = MAGIC) -> List[Any]:
    """The parts of a blob: ``head`` (with ``"leaves"`` added) under
    ``magic``, then one view for each array."""
    leaves, views = [], []
    for a in map(np.asarray, arrays):
        order = _memory_order(a)
        if order is None:
            leaves.append((_dtype_tag(a.dtype), a.shape))
        else:                   # written as it lies, its axes noted
            leaves.append((_dtype_tag(a.dtype), a.shape, order))
            a = a.transpose(order)
        views.append(byte_view(a))
    # zero fill: ``pickle.loads`` ignores what follows the pickle
    return [lead(magic, pickle.dumps(dict(head, leaves=leaves)), b"\0")
            ] + views


def unpack_arrays(blob: Any, magic: bytes = MAGIC
                  ) -> Optional[Tuple[Dict[str, Any], List[np.ndarray]]]:
    """``(head, arrays)`` of a blob that :func:`pack_arrays` laid out —
    the arrays are views of ``blob`` — or None where it does not start
    with ``magic``."""
    got = split_lead(blob, magic)
    if got is None:
        return None
    head, body = pickle.loads(got[0]), got[1]
    arrays, at = [], 0
    for dtype, shape, *order in head["leaves"]:
        a = np.frombuffer(body, _dtype_of(dtype),
                          int(np.prod(shape, dtype=np.int64)), at)
        at += a.nbytes
        if order:       # laid out with its axes in this order
            (order,) = order
            arrays.append(a.reshape([shape[i] for i in order]).transpose(
                np.argsort(order)))
        else:
            arrays.append(a.reshape(shape))
    return head, arrays


def pack_named(head: Dict[str, Any], **arrays: np.ndarray) -> List[Any]:
    """:func:`pack_arrays` for arrays that go by name."""
    return pack_arrays(dict(head, names=list(arrays)),
                       list(arrays.values()))


def unpack_named(blob: Any) -> Tuple[Dict[str, Any], Any]:
    """``(head, arrays by name)`` of a :func:`pack_named` blob, or of
    one saved before it: a pickled dict whose ``"npz"`` holds them."""
    got = unpack_arrays(blob)
    if got is None:
        head = pickle.loads(blob)
        return head, np.load(io.BytesIO(head["npz"]))
    head, arrays = got
    return head, dict(zip(head["names"], arrays))
