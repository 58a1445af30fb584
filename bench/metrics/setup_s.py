"""Process start to the first timed chunk: building the corpus, weights and
session, compiling (or loading from the persistent cache) and warming up."""


def read(run):
    return run.setup_s
