"""Custom C++ op extension builder (reference:
python/paddle/utils/cpp_extension/ — CUDAExtension/CppExtension/load
compile user .cc/.cu with PD_BUILD_OP macros into loadable paddle ops
with autograd integration).

TPU-native shape: device compute belongs in Pallas kernels (see
ops/pallas_ops.py); a custom C++ op here is HOST compute — pre/post
processing, tokenizers, lookup logic — that still composes with the
framework: it runs under jit (XLA host callback via
``jax.pure_callback``), takes/returns ``Tensor`` through the autograd
tape, and participates in backward when a gradient function is
exported.

The C ABI replaces the reference's PD_BUILD_OP macro. Export from your
.cc (extern "C"):

    // forward: inputs are float32 arrays of identical shape; out has
    // the same shape (elementwise-family contract)
    void pd_op_<NAME>(const float** ins, int n_ins, float* out,
                      const int64_t* shape, int ndim);
    // optional backward: fill one input-gradient per input
    void pd_grad_<NAME>(const float** ins, int n_ins,
                        const float* gout, float** gins,
                        const int64_t* shape, int ndim);

``load(name, sources)`` compiles with g++, discovers every pd_op_*
symbol, and returns a module-like object whose attributes are the ops.
The raw ``ctypes.CDLL`` stays available as ``.cdll`` for free-form
native libraries (the csrc/ runtime pattern).
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import tempfile

import numpy as np

__all__ = ["CppExtension", "load", "get_build_directory",
           "CustomOpModule"]

_F32P = ctypes.POINTER(ctypes.c_float)
_F32PP = ctypes.POINTER(_F32P)
_I64P = ctypes.POINTER(ctypes.c_int64)


def get_build_directory():
    d = os.environ.get("PADDLE_TPU_EXTENSION_DIR",
                       os.path.join(tempfile.gettempdir(),
                                    "paddle_tpu_extensions"))
    os.makedirs(d, exist_ok=True)
    return d


class CppExtension:
    def __init__(self, sources, extra_compile_args=None, **kwargs):
        self.sources = list(sources)
        self.extra_compile_args = list(extra_compile_args or [])


def _exported_ops(so_path):
    """pd_op_* / pd_grad_* symbols in the shared object (nm -D)."""
    try:
        out = subprocess.run(["nm", "-D", so_path], check=True,
                             capture_output=True, text=True).stdout
    except (OSError, subprocess.CalledProcessError) as e:
        import warnings
        warnings.warn(
            f"cpp_extension: cannot enumerate symbols of {so_path} "
            f"({e}); no pd_op_* custom ops will be registered — use "
            f".cdll for raw ctypes access", RuntimeWarning)
        return [], []
    fwd, bwd = [], []
    for line in out.splitlines():
        parts = line.split()
        if len(parts) >= 3 and parts[-2] in ("T", "W", "t", "w"):
            sym = parts[-1]
            if sym.startswith("pd_op_"):
                fwd.append(sym[len("pd_op_"):])
            elif sym.startswith("pd_grad_"):
                bwd.append(sym[len("pd_grad_"):])
    return fwd, bwd


class CustomOp:
    """One registered custom op: Tensor-in/Tensor-out, jit-safe,
    differentiable when the library exports pd_grad_<name>."""

    def __init__(self, name, cdll, has_grad):
        self.__name__ = name
        self._fwd = getattr(cdll, "pd_op_" + name)
        self._fwd.restype = None
        self._fwd.argtypes = [_F32PP, ctypes.c_int, _F32P, _I64P,
                              ctypes.c_int]
        self._bwd = None
        if has_grad:
            self._bwd = getattr(cdll, "pd_grad_" + name)
            self._bwd.restype = None
            self._bwd.argtypes = [_F32PP, ctypes.c_int, _F32P, _F32PP,
                                  _I64P, ctypes.c_int]
        self._jax_fn = self._build()

    # -- host callbacks ---------------------------------------------------
    def _ptrs(self, arrs):
        return (_F32P * len(arrs))(*[a.ctypes.data_as(_F32P)
                                     for a in arrs])

    def _run_fwd(self, *arrays):
        arrs = [np.ascontiguousarray(a, np.float32) for a in arrays]
        out = np.empty_like(arrs[0])
        shape = np.asarray(arrs[0].shape or (1,), np.int64)
        self._fwd(self._ptrs(arrs), len(arrs),
                  out.ctypes.data_as(_F32P),
                  shape.ctypes.data_as(_I64P), arrs[0].ndim)
        return out

    def _run_bwd(self, gout, *arrays):
        arrs = [np.ascontiguousarray(a, np.float32) for a in arrays]
        g = np.ascontiguousarray(gout, np.float32)
        gins = [np.zeros_like(a) for a in arrs]
        shape = np.asarray(arrs[0].shape or (1,), np.int64)
        self._bwd(self._ptrs(arrs), len(arrs),
                  g.ctypes.data_as(_F32P), self._ptrs(gins),
                  shape.ctypes.data_as(_I64P), arrs[0].ndim)
        return tuple(gins)

    # -- jax integration --------------------------------------------------
    def _build(self):
        import jax
        import jax.numpy as jnp
        name = self.__name__

        def call(*xs):
            # the C ABI is float32; cast INSIDE the differentiated fn
            # so cotangents chain back to the caller's dtype
            xs = tuple(jnp.asarray(x, jnp.float32) for x in xs)
            if not any(isinstance(x, jax.core.Tracer) for x in xs):
                # eager: run the C function directly on host numpy —
                # no callback machinery
                return jnp.asarray(
                    self._run_fwd(*[np.asarray(x) for x in xs]))
            spec = jax.ShapeDtypeStruct(xs[0].shape, np.float32)
            return jax.pure_callback(self._run_fwd, spec, *xs)

        # ALWAYS wrap in custom_vjp: a bare pure_callback has no JVP
        # rule, so jax.vjp over it (which apply_op takes whenever an
        # input requires grad) would crash the FORWARD pass even for
        # users who never call backward()
        @jax.custom_vjp
        def op(*xs):
            return call(*xs)

        def fwd(*xs):
            return call(*xs), tuple(jnp.asarray(x, jnp.float32)
                                    for x in xs)

        if self._bwd is None:
            def bwd(res, g):
                raise NotImplementedError(
                    f"custom op {name!r} exports no pd_grad_{name}; "
                    f"it cannot be differentiated")
        else:
            def bwd(res, g):
                if not any(isinstance(x, jax.core.Tracer)
                           for x in (g, *res)):
                    return tuple(
                        jnp.asarray(a) for a in self._run_bwd(
                            np.asarray(g),
                            *[np.asarray(x) for x in res]))
                specs = tuple(jax.ShapeDtypeStruct(x.shape, np.float32)
                              for x in res)
                return jax.pure_callback(self._run_bwd, specs, g, *res)

        op.defvjp(fwd, bwd)
        op.__name__ = name
        return op

    def __call__(self, *xs):
        from ..framework.tensor import Tensor, apply_op
        has_tensor = any(isinstance(x, Tensor) for x in xs)
        xs = tuple(x if isinstance(x, Tensor)
                   else np.asarray(x, np.float32) for x in xs)
        shapes = {tuple(x.shape) for x in xs}
        if len(shapes) > 1:
            raise ValueError(
                f"{self.__name__}: all inputs must share one shape "
                f"(elementwise-family custom op contract)")
        if has_tensor:
            # through the dispatch funnel: tape-recorded like any
            # framework op, so Tensor.backward() reaches pd_grad_*
            return apply_op(self._jax_fn, *xs, _op_name=self.__name__)
        return self._jax_fn(*xs)


class CustomOpModule:
    def __init__(self, cdll, ops):
        self.cdll = cdll
        self._ops = ops
        for name, op in ops.items():
            setattr(self, name, op)

    def __iter__(self):
        return iter(self._ops)

    def operators(self):
        return dict(self._ops)


def load(name, sources, extra_cxx_cflags=None, build_directory=None,
         verbose=False):
    """Compile C++ sources into <name>.so; return a CustomOpModule
    exposing every pd_op_* symbol as a framework op (or, with no such
    symbols, use ``.cdll`` for raw ctypes access)."""
    build_dir = build_directory or get_build_directory()
    out = os.path.join(build_dir, f"{name}.so")
    srcs = [os.path.abspath(s) for s in sources]
    newest_src = max(os.path.getmtime(s) for s in srcs)
    if not (os.path.exists(out) and os.path.getmtime(out) >= newest_src):
        # compile to a tmp and os.replace: a concurrent load() in
        # another process never dlopens a half-written .so (same
        # recipe as utils/native_build.py)
        tmp = f"{out}.{os.getpid()}.tmp"
        cmd = ["g++", "-O2", "-fPIC", "-shared", "-std=c++17",
               *(extra_cxx_cflags or []), "-o", tmp, *srcs]
        if verbose:
            print("[cpp_extension]", " ".join(cmd))
        try:
            subprocess.run(cmd, check=True,
                           capture_output=not verbose)
            os.replace(tmp, out)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
    cdll = ctypes.CDLL(out)
    fwd, bwd = _exported_ops(out)
    ops = {n: CustomOp(n, cdll, has_grad=n in bwd) for n in fwd}
    return CustomOpModule(cdll, ops)
