"""Required work of the wide-matrix reductions in one train: SanityChecker's
column statistics (mean, variance, min, max, correlation with the label: 8
operations a cell, one read of the stored matrix over the rows it samples)
and the write of the feature matrix itself."""

FAMILY = None     # the winner whose refit this file counts


def required(shape, won):
    n, d, b = shape["rows"], shape["columns"], shape["storage_bytes"]
    sampled = min(n, shape["sanity_checker"]["sample_upper_limit"])
    return 8.0 * sampled * d, float(n * d * b + sampled * d * b)
