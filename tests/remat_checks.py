"""Where a differentiated program runs the flash kernels: shared by
``test_fused_loss_remat.py`` (the model alone) and
``test_train_step_bound.py`` (``make_train_step``'s bound step)."""

import collections

import jax


def flash_kernel_calls(jaxpr, recomputing=False, out=None):
    """``{(kernel name, inside a rematerialised region): calls}`` of the
    ``pallas_call``s of a jaxpr. A layer's forward pass is outside every
    ``remat2`` equation, its backward pass (what it recomputes and what it
    differentiates) inside one; a scan body counts once."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out[eqn.params["name"], recomputing] += 1
        inside = recomputing or eqn.primitive.name == "remat2"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            flash_kernel_calls(sub, inside, out)
    return out


def assert_flash_forward_runs(calls, bodies, recomputed):
    """``bodies`` layer bodies each run the forward kernel once going
    forward and both backward kernels going back; the backward pass runs
    the forward kernel again only where ``recomputed``."""
    assert calls["flash_attention_fwd", False] == bodies, calls
    assert calls["flash_attention_fwd", True] == (
        bodies if recomputed else 0), calls
    assert calls["flash_attention_bwd_dq", True] == bodies, calls
    assert calls["flash_attention_bwd_dkv", True] == bodies, calls
