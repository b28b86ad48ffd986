"""The two parts of a language model's loss where the train step computes a
prediction module's beside the main one (``zoo.transformer`` with
``predict_ahead``): the step hands them out apart, as ``"losses"`` of its
fourth output, and whoever fetches the loss fetches that pair with it and
hands it here.

- ``dl4j_lm_main_loss``: the next-token loss of the newest recorded step;
- ``dl4j_lm_mtp_loss``: its predicted-token loss (the token after next),
  before the weight it enters the step's loss with.
"""

from __future__ import annotations

import numpy as np


def record_losses(losses) -> dict:
    """Set the gauges from one fetched step's float32 [main, predicted-token]
    pair; returns what it read."""
    from . import get_registry
    main, ahead = (float(v) for v in np.asarray(losses, np.float64))
    reg = get_registry()
    reg.gauge("dl4j_lm_main_loss",
              "next-token loss of the newest recorded step").set(main)
    reg.gauge("dl4j_lm_mtp_loss",
              "predicted-token loss (the token after next) of the newest "
              "recorded step, unweighted").set(ahead)
    return {"main": main, "mtp": ahead}
