"""How close the query's device time is to what HBM allows: the least bytes
it must read (rows x 4 B x the columns its text names, the suite's sidecar)
over the device's HBM peak (peaks.json), over the busiest device's busy time
a query. HBM-bound: the queries do a few operations a byte. On several
chips each reads its share, so the least time is divided by their number."""

UNIT = "%"
LAYER = "operators"
SOURCE = "device_trace"
MOVES = "query_p50_s"


def read(record: dict):
    trace = record["trace"]
    if not trace or not trace["devices"] or not record["peaks"]:
        return None
    least = [record["least_bytes"].get(q["query"]) for q in record["queries"]]
    if None in least or not trace["busiest_busy_s"]:
        return None
    least_s = sum(least) / record["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / len(trace["devices"]) / trace["busiest_busy_s"]
