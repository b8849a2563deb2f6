"""Pure-jnp oracle for the 2-D DFT kernel: XLA's own FFT, in the form the
library ran before the kernel (the shifts around an ortho ``fft2``)."""

import jax.numpy as jnp


def dft2c_ref(x, inverse=False, centered=True):
    """Ortho-normalised 2-D DFT over the trailing two dims; ``centered``
    puts k = 0 (and x = 0) at index N // 2 (fftshift . fft2 . ifftshift)."""
    axes = (-2, -1)
    if centered:
        x = jnp.fft.ifftshift(x, axes=axes)
    x = (jnp.fft.ifft2(x, axes=axes, norm="ortho") if inverse
         else jnp.fft.fft2(x, axes=axes, norm="ortho"))
    if centered:
        x = jnp.fft.fftshift(x, axes=axes)
    return x
