"""KvVariable: Python API over the C++ host embedding store.

Parity with tfplus's Python surface (tfplus/python/ops/
kv_variable_ops.py ``get_kv_variable``, embedding_ops.py lookups,
python/training/*.py sparse optimizers) without TensorFlow: the store
is plain C++ behind ctypes (built on demand with g++, the same
just-in-time native build idea as atorch's op builder,
atorch/ops/op_builder/builder.py), and ``embedding_lookup`` bridges it
into jitted JAX programs with ``jax.pure_callback``.

Training flow (PS-style, host-resident sparse state):

    vals = embedding_lookup(kv, keys)        # inside jit, via callback
    ... dense math on TPU ...
    grads = jax.grad(...)                    # d loss / d vals
    kv.apply_gradients("adam", keys, grads, step)   # fused C++ apply
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Dict, Optional, Tuple

from dlrover_tpu.common.config import cache_dir
from dlrover_tpu.common.log import get_logger

logger = get_logger("kv_variable")

import numpy as np

_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "native",
    "kv_store.cc",
)
_LIB_LOCK = threading.Lock()
_LIB: Optional[ctypes.CDLL] = None


def _build_library() -> str:
    """Compile kv_store.cc to a cached .so keyed by source hash."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    so_path = os.path.join(cache_dir("native"), f"kv_store_{digest}.so")
    if os.path.exists(so_path):
        return so_path
    tmp = so_path + f".build{os.getpid()}"
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        "-o", tmp, _SRC,
    ]
    subprocess.run(cmd, check=True, capture_output=True)
    os.replace(tmp, so_path)  # atomic vs concurrent builders
    return so_path


def _lib() -> ctypes.CDLL:
    global _LIB
    with _LIB_LOCK:
        if _LIB is None:
            lib = ctypes.CDLL(_build_library())
            lib.kv_create.restype = ctypes.c_void_p
            lib.kv_create.argtypes = [
                ctypes.c_int, ctypes.c_uint64, ctypes.c_int,
                ctypes.c_float, ctypes.c_int,
            ]
            lib.kv_destroy.argtypes = [ctypes.c_void_p]
            lib.kv_size.restype = ctypes.c_int64
            lib.kv_size.argtypes = [ctypes.c_void_p]
            lib.kv_set_disk_tier.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int64,
            ]
            lib.kv_set_disk_tier.restype = ctypes.c_int
            lib.kv_ram_size.argtypes = [ctypes.c_void_p]
            lib.kv_ram_size.restype = ctypes.c_int64
            lib.kv_disk_size.argtypes = [ctypes.c_void_p]
            lib.kv_disk_size.restype = ctypes.c_int64
            lib.kv_dim.restype = ctypes.c_int
            lib.kv_dim.argtypes = [ctypes.c_void_p]
            i64p = np.ctypeslib.ndpointer(np.int64, flags="C")
            f32p = np.ctypeslib.ndpointer(np.float32, flags="C")
            u32p = np.ctypeslib.ndpointer(np.uint32, flags="C")
            lib.kv_gather_or_insert.argtypes = [
                ctypes.c_void_p, i64p, ctypes.c_int64, f32p,
            ]
            lib.kv_gather_or_zeros.argtypes = [
                ctypes.c_void_p, i64p, ctypes.c_int64, f32p,
            ]
            lib.kv_update.argtypes = [
                ctypes.c_void_p, i64p, ctypes.c_int64, f32p,
                ctypes.c_int64,
            ]
            lib.kv_evict.restype = ctypes.c_int64
            lib.kv_evict.argtypes = [
                ctypes.c_void_p, ctypes.c_uint32, ctypes.c_int64,
            ]
            lib.kv_export.restype = ctypes.c_int64
            lib.kv_export.argtypes = [
                ctypes.c_void_p, ctypes.c_int64, i64p, f32p, u32p,
                i64p, ctypes.c_int64,
            ]
            lib.kv_import.argtypes = [
                ctypes.c_void_p, i64p, f32p, u32p, i64p,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_adagrad.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, i64p, f32p,
                ctypes.c_int64, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_adam.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_ftrl.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_momentum.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, i64p, f32p,
                ctypes.c_int64, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_sgd.argtypes = [
                ctypes.c_void_p, i64p, f32p, ctypes.c_int64,
                ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_group_adam.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, i64p, f32p,
                ctypes.c_int64, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_group_ftrl.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_lamb.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_adabelief.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_amsgrad.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, i64p, f32p, ctypes.c_int64,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_radam.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_adadelta.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_adahessian.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_rmsprop.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_adamax.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_nadam.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_adadqh.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_group_adadqh.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, i64p, f32p, ctypes.c_int64,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_int64,
            ]
            lib.kv_sparse_apply_lamb_hessian.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                i64p, f32p, f32p, ctypes.c_int64, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_int64,
            ]
            lib.kv_sparse_apply_group_lamb_hessian.argtypes = [
                ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                ctypes.c_void_p, ctypes.c_void_p, i64p, f32p, f32p,
                ctypes.c_int64, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_float,
                ctypes.c_float, ctypes.c_float, ctypes.c_int64,
            ]
            _LIB = lib
    return _LIB


_INIT_RANDOM, _INIT_ZEROS, _INIT_CONST = 0, 1, 2


def _gather_or_zeros(lib, handle, keys: np.ndarray, dim: int):
    """Shared non-inserting gather: [n] int64 keys -> [n, dim] f32
    (zeros for absent keys) from any store handle."""
    keys = np.ascontiguousarray(keys, np.int64)
    out = np.empty((keys.size, dim), np.float32)
    lib.kv_gather_or_zeros(handle, keys, keys.size, out)
    return out


class _Store:
    """RAII over one C++ KvStore."""

    def __init__(self, dim, seed, shards, init_scale, init_mode):
        self._lib = _lib()
        self.dim = dim
        self._h = ctypes.c_void_p(
            self._lib.kv_create(dim, seed, shards, init_scale, init_mode)
        )

    def __del__(self):
        h, self._h = self._h, None
        if h:
            self._lib.kv_destroy(h)

    @property
    def handle(self):
        return self._h

    def __len__(self):
        return self._lib.kv_size(self._h)


class KvVariable:
    """Dynamically-growing embedding table keyed by int64 ids.

    (ref: get_kv_variable, tfplus python/ops/kv_variable_ops.py; the
    C++ store carries per-key frequency/version for eviction and
    incremental export, kv_variable.h.)
    """

    def __init__(
        self,
        name: str,
        embedding_dim: int,
        seed: int = 0,
        num_shards: int = 16,
        init_scale: float = 0.05,
        disk_tier_path: Optional[str] = None,
        max_ram_rows: int = 0,
    ):
        self.name = name
        self.embedding_dim = embedding_dim
        self._store = _Store(
            embedding_dim, seed, num_shards, init_scale, _INIT_RANDOM
        )
        # optimizer slot stores, created lazily per optimizer
        self._slots: Dict[str, _Store] = {}
        # which optimizer last wrote the slots (several families
        # share the "m"/"v" names with different semantics)
        self._last_optimizer: Optional[str] = None
        self._seed = seed
        self._num_shards = num_shards
        self._disk_tier_path = disk_tier_path
        self._max_ram_rows = max_ram_rows
        # Single-host replay fence: client_id -> highest apply_seq
        # absorbed (the in-process analogue of PsServer._part_seqs —
        # one mark per client, since there is no partition movement
        # on this path). Fenced applies at or below the mark are
        # replayed duplicates and no-op.
        self._fence_seqs: Dict[int, int] = {}
        if disk_tier_path and max_ram_rows > 0:
            self.enable_disk_tier(disk_tier_path, max_ram_rows)

    def enable_disk_tier(self, path: str, max_ram_rows: int) -> None:
        """Hybrid storage (ref tfplus hybrid_embedding/): keep at most
        ``max_ram_rows`` rows resident; the coldest (lowest
        frequency, oldest version) spill to ``path`` and promote back
        on access. Checkpoints/export cover both tiers. Optimizer
        slot stores stay RAM-only (their rows are touched exactly
        when the param row is — spilling them separately would double
        the IO for no memory win on the hot path)."""
        if max_ram_rows < self._num_shards:
            # budget granularity is per shard with a floor of one
            # resident row, so the effective cap is num_shards
            logger.warning(
                "max_ram_rows=%d < num_shards=%d: effective resident "
                "cap is %d",
                max_ram_rows, self._num_shards, self._num_shards,
            )
        rc = self._store._lib.kv_set_disk_tier(
            self._store.handle, path.encode(), max_ram_rows
        )
        if rc != 0:
            raise OSError(
                f"cannot enable disk tier at {path!r} (already "
                "enabled, or file not writable)"
            )

    def ram_rows(self) -> int:
        return self._store._lib.kv_ram_size(self._store.handle)

    def disk_rows(self) -> int:
        return self._store._lib.kv_disk_size(self._store.handle)

    def __len__(self) -> int:
        return len(self._store)

    # -- lookup -------------------------------------------------------------

    def gather(self, keys: np.ndarray, train: bool = True) -> np.ndarray:
        """[n] int64 -> [n, dim] f32. train=True inserts missing keys
        (GatherOrInsert); train=False returns zeros (GatherOrZeros)."""
        keys = np.ascontiguousarray(keys, np.int64)
        if train:
            out = np.empty(
                (keys.size, self.embedding_dim), np.float32
            )
            self._store._lib.kv_gather_or_insert(
                self._store.handle, keys.ravel(), keys.size, out
            )
        else:
            out = _gather_or_zeros(
                self._store._lib, self._store.handle, keys.ravel(),
                self.embedding_dim,
            )
        return out.reshape(keys.shape + (self.embedding_dim,))

    def assign(self, keys: np.ndarray, values: np.ndarray, step: int = 0):
        keys = np.ascontiguousarray(keys, np.int64).ravel()
        values = np.ascontiguousarray(values, np.float32).reshape(
            keys.size, self.embedding_dim
        )
        self._store._lib.kv_update(
            self._store.handle, keys, keys.size, values, step
        )

    # -- optimizer slots ----------------------------------------------------

    def _slot(self, slot_name: str, init_mode=_INIT_ZEROS, init=0.0):
        if slot_name not in self._slots:
            self._slots[slot_name] = _Store(
                self.embedding_dim,
                self._seed + hash(slot_name) % 1000,
                self._num_shards,
                init,
                init_mode,
            )
        return self._slots[slot_name]

    def gather_slot(self, slot_name: str, keys) -> np.ndarray:
        """[n] int64 -> [n, dim] f32 rows of an optimizer slot store
        (zeros for keys the optimizer has not touched). Raises on a
        slot name no optimizer has created — silent zeros would mask
        typos."""
        if slot_name not in self._slots:
            if not self._slots:
                # no optimizer ran yet: every slot is all-zeros
                return np.zeros(
                    (np.asarray(keys).size, self.embedding_dim),
                    np.float32,
                )
            raise KeyError(
                f"unknown slot {slot_name!r}; existing: "
                f"{sorted(self._slots)}"
            )
        store = self._slots[slot_name]
        keys = np.ascontiguousarray(keys, np.int64).ravel()
        return _gather_or_zeros(
            store._lib, store.handle, keys, self.embedding_dim
        )

    def adadqh_hypergradients(
        self,
        keys,
        lr: float,
        step: int,
        eps: float = 1e-5,
        beta1: float = 0.9,
        beta2: float = 0.999,
    ):
        """Per-row (lr_hg, eps_hg) for keys trained with the
        ``adadqh`` family — the sparse surface of the reference's
        KvVariableComputeAdaDQHHG op (tfplus
        kv_variable/ops/training_ops.cc), built from the m/v slot
        rows and the dense hypergradient math
        (optim/adadqh.py adadqh_hypergradients, finite-diff tested).

        Refuses tables whose slots were written by a different
        optimizer: adam/lamb/... also keep "m"/"v" slots, but their v
        tracks raw-gradient moments, not AdaDQH's gradient-difference
        curvature — hypergradients computed from them would be
        numerically plausible and semantically wrong."""
        if self._last_optimizer not in (
            None, "adadqh", "group_adadqh"
        ):
            raise ValueError(
                "adadqh_hypergradients needs adadqh-family slots; "
                f"this table was last trained with "
                f"{self._last_optimizer!r}"
            )
        from dlrover_tpu.optim import adadqh_hypergradients

        m = self.gather_slot("m", keys)
        v = self.gather_slot("v", keys)
        lr_hg, eps_hg = adadqh_hypergradients(
            m, v, lr, eps, beta1, beta2, step
        )
        return np.asarray(lr_hg), np.asarray(eps_hg)

    def _hessian_rows(self, kw, optimizer, keys, ukeys, inv):
        """Validate and dedupe trainer-supplied Hutchinson Hessian-
        diagonal rows (same [n, dim] layout and duplicate-key
        combining as the gradients) for the curvature optimizers
        (adahessian, lamb_hessian, group_lamb_hessian)."""
        hessian = kw.get("hessian")
        if hessian is None:
            raise ValueError(
                f"{optimizer} requires hessian= rows aligned with "
                "keys (Hutchinson diagonal estimates)"
            )
        hessian = np.ascontiguousarray(hessian, np.float32).reshape(
            keys.size, self.embedding_dim
        )
        uhess = np.zeros((ukeys.size, self.embedding_dim), np.float32)
        np.add.at(uhess, inv, hessian)
        return uhess

    def apply_gradients(
        self,
        optimizer: str,
        keys: np.ndarray,
        grads: np.ndarray,
        step: int,
        lr: float = 1e-3,
        client_id: int = -1,
        apply_seq: int = -1,
        **kw,
    ) -> None:
        """Fused sparse apply. Duplicate keys are combined first (sum)
        — the reference's kernels expect deduplicated ids too.

        ``(client_id, apply_seq)`` with both >= 0 engages the replay
        fence: a seq at or below this client's mark is a replayed
        duplicate and becomes a no-op instead of a double-apply."""
        if client_id >= 0 and apply_seq >= 0:
            if apply_seq <= self._fence_seqs.get(client_id, -1):
                return
            self._fence_seqs[client_id] = apply_seq
        keys = np.ascontiguousarray(keys, np.int64).ravel()
        grads = np.ascontiguousarray(grads, np.float32).reshape(
            keys.size, self.embedding_dim
        )
        ukeys, inv = np.unique(keys, return_inverse=True)
        ugrads = np.zeros((ukeys.size, self.embedding_dim), np.float32)
        np.add.at(ugrads, inv, grads)

        self._last_optimizer = optimizer
        lib = self._store._lib
        h = self._store.handle
        if optimizer == "adam":
            lib.kv_sparse_apply_adam(
                h,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-8), max(step, 1),
            )
        elif optimizer == "adagrad":
            lib.kv_sparse_apply_adagrad(
                h,
                self._slot("accum").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("eps", 1e-10), step,
            )
        elif optimizer == "ftrl":
            # TF/tfplus convention: lr_power <= 0 (typically -0.5); the
            # C++ kernel computes pow(accum, -lr_power), so a positive
            # value would grow the step as the accumulator grows
            # (ref: tfplus kv_variable/kernels/training_ops.cc Ftrl
            # validation).
            lr_power = kw.get("lr_power", -0.5)
            if lr_power > 0:
                raise ValueError(
                    f"ftrl lr_power must be <= 0, got {lr_power}"
                )
            lib.kv_sparse_apply_ftrl(
                h,
                self._slot(
                    "accum_ftrl", _INIT_CONST,
                    kw.get("initial_accumulator", 0.1),
                ).handle,
                self._slot("linear").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("l1", 0.0), kw.get("l2", 0.0),
                lr_power, step,
            )
        elif optimizer == "momentum":
            lib.kv_sparse_apply_momentum(
                h,
                self._slot("momentum").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("momentum", 0.9), step,
            )
        elif optimizer in ("sgd", "gradient_descent"):
            # ref: tfplus python/training/gradient_descent.py — the
            # slot-free baseline of the fused-apply family.
            lib.kv_sparse_apply_sgd(
                h, ukeys, ugrads, ukeys.size, lr, step
            )
        elif optimizer == "group_adam":
            # Adam + group lasso (ref tfplus group_adam.py /
            # training_ops.cc:1065): rows whose L21-shrunk linear norm
            # drops below l21*sqrt(dim) collapse to exact zeros.
            lib.kv_sparse_apply_group_adam(
                h,
                self._slot("accum_ga").handle,
                self._slot("linear_ga").handle,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-8), kw.get("l1", 0.0),
                kw.get("l2", 0.0), kw.get("l21", 0.0), max(step, 1),
            )
        elif optimizer == "group_ftrl":
            lr_power = kw.get("lr_power", -0.5)
            if lr_power > 0:
                raise ValueError(
                    f"ftrl lr_power must be <= 0, got {lr_power}"
                )
            lib.kv_sparse_apply_group_ftrl(
                h,
                self._slot(
                    "accum_ftrl", _INIT_CONST,
                    kw.get("initial_accumulator", 0.1),
                ).handle,
                self._slot("linear").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("l1", 0.0), kw.get("l2", 0.0),
                kw.get("l21", 0.0), lr_power,
                kw.get("l2_shrinkage", 0.0), step,
            )
        elif optimizer == "lamb":
            lib.kv_sparse_apply_lamb(
                h,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-6),
                kw.get("weight_decay", 0.0), max(step, 1),
            )
        elif optimizer == "adabelief":
            lib.kv_sparse_apply_adabelief(
                h,
                self._slot("m").handle,
                self._slot("s").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-16), max(step, 1),
            )
        elif optimizer == "amsgrad":
            lib.kv_sparse_apply_amsgrad(
                h,
                self._slot("m").handle,
                self._slot("v").handle,
                self._slot("vhat").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-8), max(step, 1),
            )
        elif optimizer == "radam":
            lib.kv_sparse_apply_radam(
                h,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-8), max(step, 1),
            )
        elif optimizer == "adadelta":
            lib.kv_sparse_apply_adadelta(
                h,
                self._slot("accum_ad").handle,
                self._slot("accum_update").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("rho", 0.95), kw.get("eps", 1e-6), step,
            )
        elif optimizer == "adahessian":
            uhess = self._hessian_rows(kw, optimizer, keys, ukeys, inv)
            lib.kv_sparse_apply_adahessian(
                h,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, uhess, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-8),
                kw.get("hessian_power", 1.0), max(step, 1),
            )
        elif optimizer == "rmsprop":
            momentum = kw.get("momentum", 0.0)
            lib.kv_sparse_apply_rmsprop(
                h,
                self._slot("ms").handle,
                # Plain RMSProp keeps a single accumulator: don't
                # allocate a momentum table nobody reads.
                self._slot("mom_rms").handle if momentum else None,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("rho", 0.9), momentum,
                kw.get("eps", 1e-7), step,
            )
        elif optimizer == "adamax":
            lib.kv_sparse_apply_adamax(
                h,
                self._slot("m").handle,
                self._slot("u_inf").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-8), max(step, 1),
            )
        elif optimizer == "nadam":
            lib.kv_sparse_apply_nadam(
                h,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-8), max(step, 1),
            )
        elif optimizer == "adadqh":
            # Ant's quasi-Hessian family (published as AGD; dense twin
            # optim/agd.py, ref tfplus ApplyAdaDQH registrations).
            lib.kv_sparse_apply_adadqh(
                h,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-5), max(step, 1),
            )
        elif optimizer == "group_adadqh":
            # AdaDQH + group lasso (ref
            # KvVariableGroupSparseApplyAdaDQHV2): l1/l2/l21 in loss
            # units, scaled by lr inside the kernel (V2 convention).
            lib.kv_sparse_apply_group_adadqh(
                h,
                self._slot("linear_dqh").handle,
                self._slot("m").handle,
                self._slot("v").handle,
                ukeys, ugrads, ukeys.size,
                lr, kw.get("beta1", 0.9), kw.get("beta2", 0.999),
                kw.get("eps", 1e-5), kw.get("l1", 0.0),
                kw.get("l2", 0.0), kw.get("l21", 0.0), max(step, 1),
            )
        elif optimizer in ("lamb_hessian", "group_lamb_hessian"):
            # LAMB trust ratio with a curvature-driven second moment:
            # needs the same trainer-supplied Hutchinson rows as
            # adahessian.
            uhess = self._hessian_rows(kw, optimizer, keys, ukeys, inv)
            if optimizer == "lamb_hessian":
                lib.kv_sparse_apply_lamb_hessian(
                    h,
                    self._slot("m").handle,
                    self._slot("v").handle,
                    ukeys, ugrads, uhess, ukeys.size,
                    lr, kw.get("beta1", 0.9),
                    kw.get("beta2", 0.999),
                    kw.get("eps", 1e-6), max(step, 1),
                )
            else:
                lib.kv_sparse_apply_group_lamb_hessian(
                    h,
                    self._slot("accum_lh").handle,
                    self._slot("linear_lh").handle,
                    self._slot("m").handle,
                    self._slot("v").handle,
                    ukeys, ugrads, uhess, ukeys.size,
                    lr, kw.get("beta1", 0.9),
                    kw.get("beta2", 0.999),
                    kw.get("eps", 1e-6), kw.get("l1", 0.0),
                    kw.get("l2", 0.0), kw.get("l21", 0.0),
                    max(step, 1),
                )
        else:
            raise ValueError(f"unknown sparse optimizer {optimizer!r}")

    # -- eviction (under/over-flow policies) --------------------------------

    def evict(
        self, min_frequency: int = 0, min_version: int = 0
    ) -> int:
        return self._store._lib.kv_evict(
            self._store.handle, min_frequency, min_version
        )

    # -- checkpoint ---------------------------------------------------------

    def export(
        self, since_version: int = 0
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """(keys, values, freqs, versions); since_version>0 = delta
        export of rows touched at/after that step."""
        lib = self._store._lib
        h = self._store.handle
        cap = len(self._store)
        keys = np.empty(max(cap, 1), np.int64)
        values = np.empty((max(cap, 1), self.embedding_dim), np.float32)
        freqs = np.empty(max(cap, 1), np.uint32)
        versions = np.empty(max(cap, 1), np.int64)
        n = lib.kv_export(
            h, since_version, keys, values, freqs, versions, cap
        )
        if n > cap:  # store grew between size() and export
            cap = int(n)
            keys = np.empty(cap, np.int64)
            values = np.empty((cap, self.embedding_dim), np.float32)
            freqs = np.empty(cap, np.uint32)
            versions = np.empty(cap, np.int64)
            n = lib.kv_export(
                h, since_version, keys, values, freqs, versions, cap
            )
        n = int(n)
        return keys[:n], values[:n], freqs[:n], versions[:n]

    def import_(self, keys, values, freqs=None, versions=None) -> None:
        keys = np.ascontiguousarray(keys, np.int64)
        values = np.ascontiguousarray(values, np.float32)
        n = keys.size
        freqs = (
            np.ascontiguousarray(freqs, np.uint32)
            if freqs is not None
            else np.zeros(n, np.uint32)
        )
        versions = (
            np.ascontiguousarray(versions, np.int64)
            if versions is not None
            else np.zeros(n, np.int64)
        )
        self._store._lib.kv_import(
            self._store.handle, keys, values, freqs, versions, n
        )

    def state_dict(self) -> dict:
        keys, values, freqs, versions = self.export()
        slots = {}
        for name, store in self._slots.items():
            cap = len(store)
            sk = np.empty(max(cap, 1), np.int64)
            sv = np.empty((max(cap, 1), self.embedding_dim), np.float32)
            sf = np.empty(max(cap, 1), np.uint32)
            sver = np.empty(max(cap, 1), np.int64)
            n = int(
                store._lib.kv_export(
                    store.handle, 0, sk, sv, sf, sver, cap
                )
            )
            slots[name] = (sk[:n], sv[:n])
        return {
            "keys": keys,
            "values": values,
            "freqs": freqs,
            "versions": versions,
            "slots": slots,
        }

    def import_slot(self, name: str, keys, values) -> None:
        """Import optimizer-slot rows (checkpoint restore / PS move).
        Recreates the slot store with matching init semantics."""
        keys = np.ascontiguousarray(keys, np.int64)
        values = np.ascontiguousarray(values, np.float32)
        mode = _INIT_CONST if name == "accum_ftrl" else _INIT_ZEROS
        slot = self._slot(name, mode, 0.1 if mode == _INIT_CONST else 0.0)
        slot._lib.kv_update(
            slot.handle, keys, keys.size,
            values.reshape(keys.size, self.embedding_dim), 0,
        )

    def load_state_dict(self, state: dict) -> None:
        self.import_(
            state["keys"], state["values"], state.get("freqs"),
            state.get("versions"),
        )
        for name, (sk, sv) in state.get("slots", {}).items():
            self.import_slot(name, sk, sv)


class SparseOptimizer:
    """Convenience: one object applying the same rule to many
    KvVariables. Rules: sgd (alias gradient_descent) | adam |
    adagrad | ftrl | momentum | lamb | adabelief | amsgrad | radam |
    adadelta | adahessian | rmsprop | adamax | nadam | group_adam |
    group_ftrl — the group_* variants carry the reference's
    group-lasso L21 row sparsification
    (tfplus python/training/group_adam.py, sparse_group_ftrl.py;
    kernels in native/kv_store.cc)."""

    def __init__(self, optimizer: str = "adam", lr: float = 1e-3, **kw):
        self.optimizer = optimizer
        self.lr = lr
        self.kw = kw

    def apply(
        self,
        grads_by_var: Dict[KvVariable, Tuple[np.ndarray, np.ndarray]],
        step: int,
    ) -> None:
        for var, (keys, grads) in grads_by_var.items():
            var.apply_gradients(
                self.optimizer, keys, grads, step, lr=self.lr, **self.kw
            )


def embedding_lookup(kv: KvVariable, keys, train: bool = True):
    """JAX-visible lookup: usable inside jit via pure_callback.

    Returns f32 [batch..., dim]. Differentiable in the sense that the
    cotangent w.r.t. the *gathered values* flows out of jax.grad; feed
    it to ``kv.apply_gradients``. (The table itself is host state, not
    a traced array — by design, see module docstring.)
    """
    import jax
    import jax.numpy as jnp

    keys = jnp.asarray(keys)
    out_shape = jax.ShapeDtypeStruct(
        keys.shape + (kv.embedding_dim,), jnp.float32
    )

    def host_gather(k):
        return kv.gather(np.asarray(k), train=train)

    return jax.pure_callback(host_gather, out_shape, keys)
