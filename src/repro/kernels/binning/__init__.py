from .kernel import (bin_fused_matvec_pallas, bin_gather_blocked_pallas,
                     bin_scatter_blocked_pallas, route_pack_pallas,
                     route_unpack_pallas)
from .ops import (bin_fused_matvec_op, bin_loads_blocked_op, bin_loads_op,
                  bin_readout_blocked_op, bin_readout_op)
from .ref import bin_gather_ref, bin_scatter_ref
