"""Share of the cells the device compared that hold no cell of the model:
100 x (1 - Σ ``lut_cells`` / Σ ``match_cells``) over the batches formed
before the profiler started.  ``match_cells`` counts every placed cell,
padding rows, divisions and bucket rows included, times the bucket;
``lut_cells`` the banks' LUT rows times LUT columns, times the requests.
None where the batch records have no such fields."""
import spans


def read(run):
    rec = spans.unprofiled(run)
    if rec is None or not {"match_cells", "lut_cells"} <= set(
            rec.dtype.names):
        return None
    match = float(rec["match_cells"].sum())
    if match == 0:
        return None
    return 100.0 * (1.0 - float(rec["lut_cells"].sum()) / match)
