from .ops import (coil_forward, coil_adjoint, coil_lincomb, plane_mult,
                  sms_adjoint, sms_lincomb)
from .kernel import (coil_forward_pallas, coil_adjoint_pallas,
                     coil_lincomb_pallas, plane_mult_pallas,
                     sms_adjoint_pallas, sms_lincomb_pallas)
from .ref import (coil_forward_ref, coil_adjoint_ref, coil_lincomb_ref,
                  plane_mult_ref, sms_adjoint_ref, sms_lincomb_ref)

__all__ = ["coil_forward", "coil_adjoint", "coil_lincomb", "plane_mult",
           "sms_adjoint", "sms_lincomb",
           "coil_forward_pallas", "coil_adjoint_pallas",
           "coil_lincomb_pallas", "plane_mult_pallas",
           "sms_adjoint_pallas", "sms_lincomb_pallas",
           "coil_forward_ref", "coil_adjoint_ref", "coil_lincomb_ref",
           "plane_mult_ref", "sms_adjoint_ref", "sms_lincomb_ref"]
