"""All-pairs replica x ctrl reduced-energy matrix as a Pallas TPU kernel.

This is the TPU-native replacement for the paper's S-REMD 'extra Amber task
per replica': instead of launching one single-point-energy task per
(replica, ctrl) pair, per-replica features (u_base, u_elec, phi, psi) and
per-ctrl parameters (beta, salt, centers, ks) are packed into two (8, .)
arrays and the full matrix is assembled as tiled (BR x BC) outer blocks —
a few VPU ops per element, fully bandwidth-trivial, O(R*C) work instead of
O(R*C) *task launches*.

Feature rows:  0 u_base, 1 u_elec, 2 phi_deg, 3 psi_deg, 4 valid.
Ctrl rows:     0 beta, 1 salt, 2 center0, 3 center1, 4 k0, 5 k1.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _wrap(d):
    return jnp.mod(d + 180.0, 360.0) - 180.0


def _xmat_kernel(f_ref, g_ref, o_ref):
    f = f_ref[...]                   # (8, BR)
    g = g_ref[...]                   # (8, BC)
    u_base, u_elec = f[0][:, None], f[1][:, None]
    phi, psi = f[2][:, None], f[3][:, None]
    beta, salt = g[0][None, :], g[1][None, :]
    c0, c1 = g[2][None, :], g[3][None, :]
    k0, k1 = g[4][None, :], g[5][None, :]
    u = u_base + (1.0 - 0.5 * salt) * u_elec
    d0 = _wrap(phi - c0)
    d1 = _wrap(psi - c1)
    u = u + k0 * d0 * d0 + k1 * d1 * d1
    o_ref[...] = beta * u


def exchange_matrix_kernel(feat, ctrl, *, block_r: int = 128,
                           block_c: int = 128,
                           interpret: bool = False) -> jax.Array:
    """feat: (8, R), ctrl: (8, C) packed; returns (R, C) f32."""
    r, c = feat.shape[1], ctrl.shape[1]
    block_r = min(block_r, r)
    block_c = min(block_c, c)
    assert r % block_r == 0 and c % block_c == 0
    return pl.pallas_call(
        _xmat_kernel,
        grid=(r // block_r, c // block_c),
        in_specs=[pl.BlockSpec((8, block_r), lambda i, j: (0, i)),
                  pl.BlockSpec((8, block_c), lambda i, j: (0, j))],
        out_specs=pl.BlockSpec((block_r, block_c), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        name="exchange_matrix",
        interpret=interpret,
    )(feat, ctrl)
