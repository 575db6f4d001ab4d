"""Host seconds in the program's ``from_arrays`` until the chunk table is
ready in HBM."""


def read(rec):
    return rec["timings"]["index_load_s"]
