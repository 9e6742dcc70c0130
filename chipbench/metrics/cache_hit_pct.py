"""Query rows answered from the bucket-exact cache over all rows looked
up, over the window, in % (program counters)."""


def read(run):
    looked = run.info["hits"] + run.info["misses"]
    return 100.0 * run.info["hits"] / looked if looked else None
