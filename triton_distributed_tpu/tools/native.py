"""ctypes bindings for the native library (csrc/libtdt.so): the AOT
bundle loader / C runtime.

Reference analogue: the pybind'd native ops (`csrc/lib/op_pybind.cc` →
`libtriton_distributed`) and the AOT C runtime.  We bind with ctypes
(no pybind11 in the image).  The library is never committed: it is
built from source on first use (`make -C csrc`, into the gitignored
`csrc/build/`), and a failed build raises with the compiler's output.

The MoE alignment/swizzle bindings (`tdt_moe_align_block_size`,
`tdt_swizzle_*`) were DELETED in ISSUE 14 along with
`csrc/moe_align.c`: the reference needs a host/device sort because
CUDA grouped GEMM consumes ragged segments, but the TPU packed MoE
schedule (`moe_utils.plan_chunks`) is planned on-device in XLA inside
jit — a host C call has no seam on that hot path, so the parity code
was dead by construction (VERDICT r5 dead-code flag; decision
recorded in docs/analysis.md "Dead code").
"""

from __future__ import annotations

import ctypes
import functools
import os
import subprocess

_CSRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "csrc")
_LIB_PATH = os.path.join(_CSRC, "build", "libtdt.so")


@functools.lru_cache(maxsize=None)
def _load() -> ctypes.CDLL:
    if not os.path.exists(_LIB_PATH):
        res = subprocess.run(["make", "-C", _CSRC], capture_output=True,
                             text=True, timeout=300)
        if res.returncode != 0 or not os.path.exists(_LIB_PATH):
            raise RuntimeError(
                f"building {_LIB_PATH} failed (make -C {_CSRC}, "
                f"rc={res.returncode}):\n{res.stderr}")
    lib = ctypes.CDLL(_LIB_PATH)
    lib.tdt_bundle_open.restype = ctypes.c_int
    lib.tdt_bundle_open.argtypes = [ctypes.c_char_p,
                                    ctypes.POINTER(ctypes.c_void_p)]
    lib.tdt_bundle_num_variants.restype = ctypes.c_int
    lib.tdt_bundle_num_variants.argtypes = [ctypes.c_void_p]
    lib.tdt_bundle_variant_name.restype = ctypes.c_char_p
    lib.tdt_bundle_variant_name.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.tdt_bundle_load_variant.restype = ctypes.c_int
    lib.tdt_bundle_load_variant.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
    lib.tdt_executable_size.restype = ctypes.c_size_t
    lib.tdt_executable_size.argtypes = [ctypes.c_void_p]
    lib.tdt_bundle_close.argtypes = [ctypes.c_void_p]
    lib.tdt_executable_free.argtypes = [ctypes.c_void_p]
    return lib


# ---------------------------------------------------------------------------
# Native AOT bundle loader
# ---------------------------------------------------------------------------

# dtype codes shared with csrc/tdt_aot_runtime.h (tdt_dtype).
_DTYPE_CODES = {"float32": 0, "bfloat16": 1, "float16": 2, "int32": 3,
                "int64": 4, "uint8": 5, "int8": 6, "bool": 7}


def write_bundle_index(bundle_dir: str) -> None:
    """Emit index.bin (v2 TLV) for the C runtime from manifest.json.

    v2 layout per variant: name, jaxexp file, mlir file, then arg and
    output signatures (dtype code u8, rank u8, dims i64[rank]) so the
    native executor can build PJRT buffers without parsing JSON.
    """
    import json
    import struct

    with open(os.path.join(bundle_dir, "manifest.json")) as f:
        manifest = json.load(f)

    def pstr(s):
        b = s.encode()
        return struct.pack("<H", len(b)) + b

    def psig(shapes, dtypes):
        blob = struct.pack("<H", len(shapes))
        for shape, dt in zip(shapes, dtypes):
            # Unknown dtypes get code 255: the Python (.jaxexp) path
            # still works; the C executor rejects that variant at
            # execute time instead of this function raising.
            blob += struct.pack("<BB", _DTYPE_CODES.get(dt, 255),
                                len(shape))
            for dim in shape:
                blob += struct.pack("<q", dim)
        return blob

    blob = struct.pack("<III", 0x41544454, 2, len(manifest["variants"]))
    for name, v in manifest["variants"].items():
        blob += pstr(name) + pstr(v["file"]) + pstr(v.get("mlir_file", ""))
        blob += psig(v["arg_shapes"], v["arg_dtypes"])
        blob += psig(v.get("out_shapes", []), v.get("out_dtypes", []))
    with open(os.path.join(bundle_dir, "index.bin"), "wb") as f:
        f.write(blob)


def native_open_bundle(bundle_dir: str):
    """Open a bundle with the C runtime; returns (handle, names)."""
    lib = _load()
    h = ctypes.c_void_p()
    rc = lib.tdt_bundle_open(bundle_dir.encode(), ctypes.byref(h))
    if rc != 0:
        raise RuntimeError(f"tdt_bundle_open failed: rc={rc}")
    n = lib.tdt_bundle_num_variants(h)
    names = [lib.tdt_bundle_variant_name(h, i).decode() for i in range(n)]
    return h, names


def native_load_variant_size(handle, variant: str) -> int:
    lib = _load()
    e = ctypes.c_void_p()
    rc = lib.tdt_bundle_load_variant(handle, variant.encode(),
                                     ctypes.byref(e))
    if rc != 0:
        raise RuntimeError(f"load_variant failed: rc={rc}")
    size = lib.tdt_executable_size(e)
    lib.tdt_executable_free(e)
    return int(size)
