"""Mean recall of each search's page against the exact k nearest
neighbours of its query over the whole collection."""


def read(run):
    return run.recall
