"""Packing: seconds of the first ``weights_for(rung)`` from the anchor.

Host clock around ``ElasticEngine.weights_for`` ended by
``block_until_ready``: Slice-and-Scale to the rung and nibble packing at 4
bits, or, at the anchor's own rung, the packed tree built from the anchor.
"""


def read(run):
    return run.phases.get("conversion")
