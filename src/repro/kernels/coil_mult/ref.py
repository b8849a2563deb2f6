"""Pure-jnp oracle for the coil-sensitivity pointwise operators
(the paper's custom CUDA kernels: C and the channel-summed C^H)."""

import jax.numpy as jnp


def coil_forward_ref(coils, x):
    """z_j = c_j * x.  coils: (J, X, Y) complex, x: (X, Y) complex."""
    return coils * x[None]


def coil_adjoint_ref(coils, z, mask=None):
    """Sum_j conj(c_j) * z_j, optionally masked (M_Omega fused)."""
    out = jnp.sum(jnp.conj(coils) * z, axis=0)
    if mask is not None:
        out = out * mask
    return out


def coil_lincomb_ref(a, x, b=None, y=None, scale=None):
    """Generalized coil linear combination in one pass:

        out_j = scale * (a * x_j + b * y_j)

    ``a``/``b``: (X, Y) complex planes, ``x``/``y``: (J, X, Y) coil
    stacks, ``scale``: (X, Y) real plane (or None).  ``b=None`` drops the
    second term.  Covers the NLINV pointwise chains ``fov*(rho*c)`` (G)
    and ``fov*(drho*c0 + rho0*dc)`` (DG) without intermediates."""
    out = a[None] * x
    if b is not None:
        out = out + b[None] * y
    if scale is not None:
        out = scale[None] * out
    return out


def plane_mult_ref(z, m):
    """Broadcast real-plane multiply ``z_j * m`` (the mask / FOV / Sobolev
    weight application fused as one pointwise pass)."""
    return z * m[None] if z.ndim == m.ndim + 1 else z * m


def sms_lincomb_ref(a, x, b=None, y=None, scale=None, *, mix):
    """Slice-mixed coil linear combination (the SMS forward chain):

        out_qj = scale * Sum_m mix[q, m] * (a_m * x_mj + b_m * y_mj)

    ``a``/``b``: (M, X, Y) complex slice planes, ``x``/``y``: (M, J, X, Y)
    coil stacks, ``scale``: (X, Y) real plane (or None), ``mix``: the
    (Q, M) complex partition-encoding matrix (a host constant).
    ``b=None`` drops the second term."""
    t = a[:, None] * x
    if b is not None:
        t = t + b[:, None] * y
    out = jnp.stack([sum(complex(mix[q, m]) * t[m]
                         for m in range(t.shape[0]))
                     for q in range(mix.shape[0])])
    if scale is not None:
        out = scale * out
    return out


def sms_adjoint_ref(v, coils, scale, g, *, mix):
    """Slice-unmixed adjoint chain (the SMS DG^H pointwise part): with

        z_mj = scale * Sum_q conj(mix[q, m]) * v_qj

    returns ``(Sum_j conj(coils_mj) * z_mj, g_m * z_mj)``: the channel
    sum (M, X, Y) and the coil-update stack (M, J, X, Y).  ``v``: (Q, J,
    X, Y), ``coils``: (M, J, X, Y), ``scale``: (X, Y) real, ``g``: (M, X,
    Y) complex."""
    z = jnp.stack([sum(complex(mix[q, m]).conjugate() * v[q]
                       for q in range(v.shape[0]))
                   for m in range(mix.shape[1])])
    z = scale * z
    return jnp.sum(jnp.conj(coils) * z, axis=1), g[:, None] * z
