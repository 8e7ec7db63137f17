"""Device seconds a step in the noise of block diffusion (scope ``embed/noise``: a sequence's checksum, the threefry draws of
a level a block and a uniform a position, the comparison, the noised ids and the concatenation ``[noised ; clean]``; drawn
for the embedding and again for the loss's weights where XLA does not merge the two) (``sdar_scopes.py``)."""

import sdar_scopes

NAME, UNIT, SOURCE = "diffusion_noise_device_s", "s", "device_trace"
LAYER = "device compute"
MOVES = "step_p50_s"


def compute(run):
    return sdar_scopes.seconds(run, NAME)
