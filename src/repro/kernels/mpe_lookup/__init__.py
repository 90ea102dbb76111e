from repro.kernels.mpe_lookup.kernel import packed_lookup_pallas
from repro.kernels.mpe_lookup.ref import packed_lookup_ref

__all__ = ["packed_lookup_pallas", "packed_lookup_ref"]
