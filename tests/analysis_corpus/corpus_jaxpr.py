"""Seeded jaxpr-pass defects — each must be flagged by the auditor.

The int32 case is the repo's own latent hazard at a scale past its
dynamic guard: ``ops._compact_mask_pairs`` ravels the (n, m) mask to
flat int32 indices, which alias once n*m crosses INT32_MAX — exactly
what ``bfm_pairs_pallas`` refuses at run time and the auditor must see
statically.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import audit_fn
from repro.kernels import ops


def _int32_overflow(report, target):
    # 60k x 60k = 3.6e9 > INT32_MAX: the ravel's flat index space
    # no longer fits the int32 iota behind nonzero()
    mask = jax.ShapeDtypeStruct((60_000, 60_000), jnp.bool_)
    audit_fn(ops._compact_mask_pairs, (mask,), target=target,
             report=report, static_kwargs=dict(max_pairs=4096),
             check_rank=False)


def _host_callback(report, target):
    def hot_path(x):
        # a host round-trip hiding inside a "pure" helper
        y = jax.pure_callback(
            lambda a: np.asarray(a) * 2,
            jax.ShapeDtypeStruct(x.shape, x.dtype), x)
        return y + 1

    audit_fn(hot_path, (jax.ShapeDtypeStruct((128,), jnp.float32),),
             target=target, report=report, check_rank=False)


def _device_transfer(report, target):
    dev = jax.devices()[0]

    def hot_path(x):
        # explicit placement inside a traced path: a real transfer,
        # unlike the benign constant device_put the auditor ignores
        return jax.device_put(x, dev) + 1

    audit_fn(hot_path, (jax.ShapeDtypeStruct((128,), jnp.float32),),
             target=target, report=report, check_rank=False)


def _rank_promotion(report, target):
    def hot_path(a, b):
        return a + b      # (64, 1) + (32,): implicit rank promotion

    audit_fn(hot_path, (jax.ShapeDtypeStruct((64, 1), jnp.float32),
                        jax.ShapeDtypeStruct((32,), jnp.float32)),
             target=target, report=report)


def _weak_output(report, target):
    def hot_path(x):
        # result dtype hangs off a Python literal only — weak-typed
        # output, silently promotable by the first caller-side op
        return jnp.full((x.shape[0],), 1.5)

    audit_fn(hot_path, (jax.ShapeDtypeStruct((64,), jnp.float32),),
             target=target, report=report, check_rank=False)


def _dtype_contract(report, target):
    def pairs_like(x):
        return x.astype(jnp.float32)   # contract says int32 pairs

    audit_fn(pairs_like, (jax.ShapeDtypeStruct((64, 2), jnp.int32),),
             target=target, report=report, check_rank=False,
             out_dtypes=(np.int32,))


def _f64_promotion(report, target):
    def hot_path(x):
        return x.astype(jnp.float64).cumsum()

    with jax.enable_x64(True):
        audit_fn(hot_path, (jax.ShapeDtypeStruct((64,), jnp.float32),),
                 target=target, report=report, check_rank=False)


CASES = [
    dict(name="int32_mask_ravel_overflow", pass_name="jaxpr",
         code="J_INT32_INDEX", audit=_int32_overflow),
    dict(name="pure_callback_in_hot_path", pass_name="jaxpr",
         code="J_CALLBACK", audit=_host_callback),
    dict(name="device_put_in_hot_path", pass_name="jaxpr",
         code="J_CALLBACK", audit=_device_transfer),
    dict(name="implicit_rank_promotion", pass_name="jaxpr",
         code="J_RANK_PROMOTION", audit=_rank_promotion),
    dict(name="weak_typed_output", pass_name="jaxpr",
         code="J_WEAK_OUT", audit=_weak_output),
    dict(name="pairs_dtype_contract", pass_name="jaxpr",
         code="J_DTYPE_CONTRACT", audit=_dtype_contract),
    dict(name="float64_promotion", pass_name="jaxpr",
         code="J_F64", audit=_f64_promotion),
]
