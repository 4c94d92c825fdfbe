"""setup_s: process start until the measured window opens (loading,
data or weights, lowering, compiling or reading compiled programs,
warm-up).  Host clock."""


def read(run, cell):
    return run.data["setup_s"]
