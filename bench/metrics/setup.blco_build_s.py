"""Host seconds of the program's ``build_blco`` on the run's tensor."""


def read(rec):
    return rec["setup"]["blco_build_s"]
