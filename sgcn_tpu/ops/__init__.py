from .pspmm import (halo_exchange, spmm_local, spmm_ell, pspmm,
                    pspmm_exchange, pspmm_overlap, pspmm_ell_sym,
                    pspmm_ell_sym_coo, pspmm_stale)

__all__ = ["halo_exchange", "spmm_local", "spmm_ell", "pspmm",
           "pspmm_exchange", "pspmm_overlap", "pspmm_ell_sym",
           "pspmm_ell_sym_coo", "pspmm_stale"]
