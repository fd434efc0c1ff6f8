"""Median over the traced window's requests of what the mesh tier's
exchanges carry: the ``mesh_exchange_bytes`` counter of the requests that
hold a ``mesh.execute`` span, / 1e6. That counter is the operand of every
collective the program's exchanges issue (``all_to_all``, ``all_gather``,
``pmax``, ``ppermute``), summed over the chips of the mesh, counted from the
planned buffers' shapes when the program is traced and kept with the
cached executable (`parallel/exchange.py collective_tally`): the padded
capacities cross the ICI, not the live rows. A program from before the
counter reports none."""

import os
import runpy

LAYERS = runpy.run_path(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "layer_rows.py"))

UNIT = "MB"
LAYER = "exchange, mesh tier"
SOURCE = "program_counter"
MOVES = "query_p50_s"


def read(record: dict):
    def carried(row):
        if "mesh.execute" not in row["self_s"]:
            return None
        nbytes = row["counters"].get("mesh_exchange_bytes")
        return None if nbytes is None else nbytes / 1e6

    return LAYERS["median"](record, carried)
