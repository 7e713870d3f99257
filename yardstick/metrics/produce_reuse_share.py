"""Input pipeline. Share of the window's batches that the iterator gathered
into memory it had used before, and not into a fresh allocation whose
pages the gather has to touch first: of the program's ``produce`` spans
(cat ``data``) that say where their batch went (``reused``, which
``ArrayDataSetIterator(shuffle=True)`` reports of every batch it gathers),
the share that say true, in %. None where no span says: a program that
records no such argument, an iterator that gathers nothing."""


def read(obs):
    said = obs.spans.args("produce", "reused", cat="data")
    if not said:
        return None
    return 100.0 * sum(map(bool, said)) / len(said)
