"""Work each sparse-path kernel must do, from its shapes.

Required bytes are the rows the operation must read and write and its
indices, not the tiles an implementation happens to fetch, so the count
holds whatever implements the operation. The least time a call can take is
the larger of its bytes over the HBM bandwidth and its FLOPs over the peak.
"""


def gather_rows(n: int, dim: int, table_itemsize: int,
                out_itemsize: int) -> dict:
    """``out[i] = table[idx[i]]`` for ``n`` indices: read ``n`` rows and the
    indices, write ``n`` rows."""
    return {"bytes": n * dim * (table_itemsize + out_itemsize) + 4 * n,
            "flops": 0}


def segment_rowsum(rows: int, segments: int, dim: int,
                   in_itemsize: int) -> dict:
    """``out[ids[i]] += values[i]`` over ``rows`` rows into ``segments``
    float32 rows: read the values and ids, write the sums; one add per
    element."""
    return {"bytes": rows * dim * in_itemsize + 4 * rows + 4 * segments * dim,
            "flops": rows * dim}


def least_seconds(work: dict, peaks: dict) -> float:
    return max(work["bytes"] / peaks["hbm_bytes_per_s"],
               work["flops"] / peaks["bf16_flops"])
