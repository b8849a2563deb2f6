from .ops import DFT2C, dft2c, engages as dft_engages
from .kernel import dft2c_pallas, dft_constants
from .ref import dft2c_ref

__all__ = ["DFT2C", "dft2c", "dft_engages", "dft2c_pallas", "dft_constants",
           "dft2c_ref"]
