"""TPU compute ops over CSR RowBlocks (JAX/XLA; the device-side seam).

No reference counterpart — dmlc-core has no tensor ops; these are the
TPU-native consumers that make HBM-resident CSR batches useful
(SpMV/row-gather for the XGBoost/linear-learner style downstream).
"""

from dmlc_tpu.ops.csr import (
    csr_to_padded_rows, spmv, csr_to_dense, segment_spmv, sdot_rows,
    sharded_spmv, csr_row_ids, csr_row_sum,
)

__all__ = ["csr_to_padded_rows", "spmv", "csr_to_dense", "segment_spmv",
           "sdot_rows", "sharded_spmv", "csr_row_ids", "csr_row_sum"]
