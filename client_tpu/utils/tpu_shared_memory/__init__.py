"""TPU shared-memory data plane — the CUDA-IPC replacement.

The reference moves *device* tensors between client and server processes via
``cudaIpcMemHandle_t`` (reference
src/python/library/tritonclient/utils/cuda_shared_memory/__init__.py:107-170).
TPUs have no cross-process device-buffer IPC: HBM is owned by one libtpu
process. The TPU-native equivalent (BASELINE.json north star) is a **shared
pinned host buffer**:

- a region is a POSIX shared-memory buffer both processes map;
- the client stages ``jax.Array``s into it with ONE batched device→host
  transfer for all arrays (``set_shared_memory_region_from_jax``) followed
  by one host-side memcpy per array into the mapped pages — batching N
  arrays into one ``jax.device_get`` pays the per-transfer cost once;
- host tensors (numpy / DLPack exporters) copy straight into the mapped
  pages with no intermediate buffer;
- the raw handle exchanged over the wire (``get_raw_handle``) is a JSON
  document carrying the shm key + framing, registered via
  ``register_tpu_shared_memory`` on either protocol client;
- the server maps the same pages and reads them zero-copy
  (``as_shared_memory_tensor`` / ``get_contents_as_numpy`` are views over
  the mapping; ``as_jax_array`` adds the one H2D transfer).

Measured copy count per staging call (device side): 1 batched D2H transfer
+ 1 host memcpy per array. The region is plain POSIX shm (not libtpu-
registered); cross-process sharing of the bytes is zero-copy, the device
boundary costs one transfer per direction.
"""

import json
import threading
import uuid
from typing import Dict, List, Optional

import numpy as np

from client_tpu.utils import (
    InferenceServerException,
    num_elements,
    serialize_byte_tensor,
    triton_to_np_dtype,
)
from client_tpu.utils import shared_memory as _system_shm
from client_tpu.utils._dlpack import SharedMemoryTensor, consume_dlpack_capsule

_allocated_lock = threading.Lock()
_allocated_regions: Dict[str, "TpuSharedMemoryRegion"] = {}

HANDLE_KIND = "tpu-host-pinned"


class TpuSharedMemoryException(InferenceServerException):
    """Raised for TPU shared-memory errors."""


class TpuSharedMemoryRegion:
    """Handle to an allocated TPU shared-memory region."""

    def __init__(self, triton_shm_name: str, byte_size: int, device_id: int):
        self._name = triton_shm_name
        self._byte_size = byte_size
        self._device_id = device_id
        self._shm_key = f"client_tpu_shm_{uuid.uuid4().hex}"
        self._base = _system_shm.create_shared_memory_region(
            triton_shm_name, self._shm_key, byte_size, create_only=True
        )

    def name(self) -> str:
        return self._name

    def key(self) -> str:
        return self._shm_key

    def byte_size(self) -> int:
        return self._byte_size

    def device_id(self) -> int:
        return self._device_id

    def buf(self, offset: int = 0, length: Optional[int] = None):
        return self._base.buf(offset, length)

    def _destroy(self) -> None:
        _system_shm.destroy_shared_memory_region(self._base)


def create_shared_memory_region(
    triton_shm_name: str, byte_size: int, device_id: int = 0
) -> TpuSharedMemoryRegion:
    """Allocate a TPU shared-memory region of ``byte_size`` bytes.

    API twin of the reference's cudaMalloc+cudaIpcGetMemHandle
    (reference cuda_shared_memory/__init__.py:107-149); here the allocation
    is a shared pinned host buffer adjacent to TPU ``device_id``.
    """
    region = TpuSharedMemoryRegion(triton_shm_name, byte_size, device_id)
    with _allocated_lock:
        _allocated_regions[triton_shm_name] = region
    return region


def get_raw_handle(shm_handle: TpuSharedMemoryRegion) -> bytes:
    """The serialized region handle to pass to register_tpu_shared_memory.

    (Reference twin: base64 of cudaIpcMemHandle reserved bytes,
    reference cuda_shared_memory/__init__.py:152-170.)
    """
    return json.dumps(
        {
            "kind": HANDLE_KIND,
            "shm_key": shm_handle.key(),
            "byte_size": shm_handle.byte_size(),
            "device_id": shm_handle.device_id(),
        }
    ).encode("utf-8")


def set_shared_memory_region(
    shm_handle: TpuSharedMemoryRegion, input_values, offset: int = 0
) -> None:
    """Copy numpy arrays into the region back-to-back from ``offset``."""
    if not isinstance(input_values, (list, tuple)):
        raise TpuSharedMemoryException(
            "input_values must be a list/tuple of arrays"
        )
    cursor = offset
    for arr in input_values:
        arr = np.asarray(arr)
        if arr.dtype == np.dtype(object) or arr.dtype.kind in ("S", "U"):
            payload = serialize_byte_tensor(arr).tobytes()
            view = shm_handle.buf(cursor, len(payload))
            view[:] = payload
            cursor += len(payload)
        else:
            arr = np.ascontiguousarray(arr)
            view = shm_handle.buf(cursor, arr.nbytes)
            # single memcpy into the shared mapping, no intermediate bytes()
            np.frombuffer(view, dtype=arr.dtype).reshape(arr.shape)[...] = arr
            cursor += arr.nbytes


def set_shared_memory_region_from_jax(
    shm_handle: TpuSharedMemoryRegion, jax_arrays, offset: int = 0
) -> None:
    """Stage jax.Arrays into the region back-to-back from ``offset``.

    ONE batched device→host transfer moves every array (``jax.device_get``
    of the whole list), then each array is memcpy'd into the mapped
    pages. Host-resident arrays skip the device transfer entirely. A
    failed transfer propagates: staging garbage would be worse.
    """
    if not isinstance(jax_arrays, (list, tuple)):
        jax_arrays = [jax_arrays]
    try:
        import jax
    except ImportError:  # numpy-only install: the inputs are host arrays
        hosts = jax_arrays
    else:
        hosts = jax.device_get(list(jax_arrays))  # ONE batched D2H transfer
    cursor = offset
    for host in hosts:
        host = np.ascontiguousarray(host)
        view = shm_handle.buf(cursor, host.nbytes)
        np.frombuffer(view, dtype=host.dtype).reshape(host.shape)[...] = host
        cursor += host.nbytes


def set_shared_memory_region_from_dlpack(
    shm_handle: TpuSharedMemoryRegion, input_values, offset: int = 0
) -> None:
    """Copy DLPack-exporting tensors (torch/jax/numpy) into the region."""
    if not isinstance(input_values, (list, tuple)):
        input_values = [input_values]
    cursor = offset
    for tensor in input_values:
        if hasattr(tensor, "__dlpack__"):
            try:
                arr = consume_dlpack_capsule(tensor.__dlpack__())
            except (ValueError, TypeError):
                # device tensor or exotic layout: stage through the host
                arr = np.asarray(tensor)
        else:
            arr = np.asarray(tensor)
        view = shm_handle.buf(cursor, arr.nbytes)
        np.frombuffer(view, dtype=arr.dtype).reshape(arr.shape)[...] = arr
        cursor += arr.nbytes


def get_contents_as_numpy(
    shm_handle: TpuSharedMemoryRegion,
    datatype,
    shape: List[int],
    offset: int = 0,
) -> np.ndarray:
    """View region contents as numpy (zero-copy for fixed-size dtypes).

    ``datatype`` may be a numpy dtype or a KServe dtype string ("BF16"...).
    """
    from client_tpu.utils import deserialize_bytes_tensor

    if isinstance(datatype, str):
        np_dtype = triton_to_np_dtype(datatype)
        if np_dtype is None:
            raise TpuSharedMemoryException(f"unknown datatype '{datatype}'")
    else:
        np_dtype = np.dtype(datatype)
    if np_dtype == np.dtype(object):
        return deserialize_bytes_tensor(bytes(shm_handle.buf(offset))).reshape(
            shape
        )
    count = num_elements(shape)
    view = shm_handle.buf(offset, count * np_dtype.itemsize)
    return np.frombuffer(view, dtype=np_dtype).reshape(shape)


def as_shared_memory_tensor(
    shm_handle: TpuSharedMemoryRegion, datatype, shape: List[int], offset: int = 0
) -> SharedMemoryTensor:
    """A DLPack-exporting tensor view over the region (zero-copy import
    into torch/numpy; reference cuda_shared_memory/__init__.py:391-399)."""
    if isinstance(datatype, str):
        np_dtype = triton_to_np_dtype(datatype)
        if np_dtype is None or np_dtype == np.dtype(object):
            raise TpuSharedMemoryException(
                f"datatype '{datatype}' cannot be viewed as a DLPack tensor"
            )
    else:
        np_dtype = np.dtype(datatype)
    count = num_elements(shape)
    view = shm_handle.buf(offset, count * np_dtype.itemsize)
    return SharedMemoryTensor(view, shape, np_dtype)


def as_jax_array(
    shm_handle: TpuSharedMemoryRegion,
    datatype,
    shape: List[int],
    offset: int = 0,
    device=None,
):
    """Import region contents as a jax.Array on ``device`` (one H2D DMA)."""
    import jax

    host = get_contents_as_numpy(shm_handle, datatype, shape, offset)
    return jax.device_put(host, device)


def allocated_shared_memory_regions() -> List[str]:
    """Names of TPU regions currently allocated by this process."""
    with _allocated_lock:
        return list(_allocated_regions.keys())


def destroy_shared_memory_region(shm_handle: TpuSharedMemoryRegion) -> None:
    """Free the region (unmap + unlink the backing shm file)."""
    with _allocated_lock:
        _allocated_regions.pop(shm_handle.name(), None)
    shm_handle._destroy()


# Fixed-layout slot ring over one region (PR-11 small-tensor fast path);
# imported late: ring.py pulls helpers from this module at call time.
from client_tpu.utils.tpu_shared_memory.ring import (  # noqa: E402
    ShmRing,
    ShmRingError,
)
