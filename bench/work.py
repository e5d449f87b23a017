"""The work each call of a cell needs, computed from the cell's shapes.

Every implementation is charged for the same work: the samples a window
delivers, at the width of the output type the configuration states, and
the paths a pricing call prices.  Nothing here asks the program.
"""
from __future__ import annotations

BYTES_PER_SAMPLE = {"uint32": 4, "float32": 4, "bfloat16": 2, "bool": 1}


def samples_per_window(cell) -> int:
    return cell.traffic["window_steps"] * cell.config["num_streams"]


def window_bytes(cell) -> int:
    """Bytes one MISRN window writes to HBM, over all its shards."""
    return (samples_per_window(cell)
            * BYTES_PER_SAMPLE[cell.config["output_type"]])


def paths_per_call(cell) -> int:
    return cell.traffic["draws_per_call"] * cell.config["num_lanes"]
