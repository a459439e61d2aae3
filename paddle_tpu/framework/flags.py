"""Runtime flag registry.

TPU-native analog of the reference flag system
(/root/reference/paddle/common/flags.h:38 PD_DEFINE_* macros,
flags_native.cc self-hosted registry; python surface
python/paddle/base/framework.py:132 set_flags / :157 get_flags).

Flags are plain Python values seeded from ``FLAGS_*`` environment variables;
subsystems read them at use-time.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable, Union

_REGISTRY: Dict[str, "_Flag"] = {}


class _Flag:
    __slots__ = ("name", "default", "value", "type", "help")

    def __init__(self, name, default, typ, help_):
        self.name = name
        self.default = default
        self.type = typ
        self.help = help_
        env = os.environ.get(name)
        self.value = self._parse(env) if env is not None else default

    def _parse(self, raw):
        if self.type is bool:
            if isinstance(raw, bool):
                return raw
            return str(raw).lower() in ("1", "true", "yes", "on")
        return self.type(raw)


def define_flag(name: str, default: Any, help_: str = "", typ=None):
    if not name.startswith("FLAGS_"):
        name = "FLAGS_" + name
    if name in _REGISTRY:
        return _REGISTRY[name]
    flag = _Flag(name, default, typ or type(default), help_)
    _REGISTRY[name] = flag
    return flag


def get_flags(names: Union[str, Iterable[str]]):
    if isinstance(names, str):
        names = [names]
    out = {}
    for n in names:
        key = n if n.startswith("FLAGS_") else "FLAGS_" + n
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {n}")
        out[n] = _REGISTRY[key].value
    return out


def set_flags(flags: Dict[str, Any]):
    for n, v in flags.items():
        key = n if n.startswith("FLAGS_") else "FLAGS_" + n
        if key not in _REGISTRY:
            raise KeyError(f"unknown flag {n}")
        f = _REGISTRY[key]
        f.value = f._parse(v)


def flag_value(name: str):
    key = name if name.startswith("FLAGS_") else "FLAGS_" + name
    return _REGISTRY[key].value


# Core flags (subset of the reference's ~244 exported flags that are
# meaningful on TPU; /root/reference/paddle/common/flags.cc).
define_flag("FLAGS_check_nan_inf", False,
            "check every op output for NaN/Inf (eager mode)")
define_flag("FLAGS_check_nan_inf_level", 0,
            "0: fatal on nan/inf; >0: log only")
define_flag("FLAGS_benchmark", False, "emit per-step timing logs")
define_flag("FLAGS_bn_pallas", False,
            "route training BatchNorm through the Pallas streaming "
            "kernels (ops/bn_pallas.py). Default OFF: measured SLOWER "
            "than XLA's BN fusions on v5e NCHW shapes (165-220 vs "
            "263-395 GB/s - the unaligned spatial lane dim defeats "
            "Pallas block DMA; the rounds-1-5 notes (git history "
            "before PR 23) round-5)")
define_flag("FLAGS_use_stride_kernel", True, "views share storage (no-op on XLA)")
define_flag("FLAGS_eager_delete_tensor_gb", 0.0, "gc threshold (XLA-managed)")
define_flag("FLAGS_low_precision_op_list", 0, "record AMP op dtype decisions")
