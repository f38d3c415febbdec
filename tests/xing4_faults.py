"""Faults put into the Xing4.0 program, each of which the comparison with
``benchmarks/reference/xing4_f32.py`` must not pass: context managers
that patch the package for as long as they are open
(``tests/test_xing4.py`` at toy widths on the CPU; a builder's chip probe
at the cell's widths: the configuration file's ``logit_check.why`` has
its readings)."""

import contextlib

from neuronx_distributed_tpu.models.xing4 import Xing4Config
from neuronx_distributed_tpu.modules import hyper_connections as hc


@contextlib.contextmanager
def _patched(owner, name, value):
    sound = getattr(owner, name)
    setattr(owner, name, value)
    try:
        yield
    finally:
        setattr(owner, name, sound)


def one_sinkhorn_iteration():
    """``H_res`` left at ``M_1``: one round of rows and columns."""
    sound = hc.sinkhorn_knopp
    return _patched(hc, "sinkhorn_knopp",
                    lambda cells, iters, eps: sound(cells, 1, eps))


def post_without_its_two():
    """``H_post = sigmoid(.)``, without its factor 2."""
    sound = hc.HyperConnection.maps

    def maps(self, x):
        pre, post, res = sound(self, x)
        return pre, [0.5 * p for p in post], res

    return _patched(hc.HyperConnection, "maps", maps)


def static_maps():
    """The three maps from their biases alone: the same for every token."""
    sound = hc.HyperConnection.maps

    def maps(self, x):
        return sound(self.clone(parent=None).bind(
            {"params": {"phi": 0.0 * self.phi, "alpha": self.alpha,
                        "bias": self.bias}}), x)

    return _patched(hc.HyperConnection, "maps", maps)


def columns_before_rows():
    """Sinkhorn's rounds the other way about: the matrix transposed in
    and out."""
    sound = hc.sinkhorn_knopp

    def swapped(cells, iters, eps):
        out = sound([list(col) for col in zip(*cells)], iters, eps)
        return [list(col) for col in zip(*out)]

    return _patched(hc, "sinkhorn_knopp", swapped)


def plain_rotary():
    """The rotary key and query rotated by the unscaled frequencies."""
    from neuronx_distributed_tpu.models.glm_moe_lite import LatentGeometry

    return _patched(Xing4Config, "rotary_rows", LatentGeometry.rotary_rows)


def scale_without_mscale():
    """The scores times ``(nope + rope)^-1/2`` alone."""
    from neuronx_distributed_tpu.models.glm_moe_lite import LatentGeometry

    return _patched(Xing4Config, "score_scale", LatentGeometry.score_scale)


#: name -> a fresh context manager; the first two are the ones the cell's
#: check is held to on the chip
FAULTS = {"one_sinkhorn_iteration": one_sinkhorn_iteration,
          "post_without_its_two": post_without_its_two,
          "static_maps": static_maps,
          "columns_before_rows": columns_before_rows,
          "plain_rotary": plain_rotary,
          "scale_without_mscale": scale_without_mscale}
