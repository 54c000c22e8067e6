"""TPU-native op foundation: activations, losses, initializers, updaters,
schedules, regularization — the replacement for DL4J's external ND4J surface
(SURVEY.md §2.11). The pallas kernels live in ``ops.flash_attention`` and
``ops.paged_attention`` (a decode step's read of the paged k/v cache) and are
imported from there at use sites only, so importing the package never pulls
in pallas.
"""

from . import activations, initializers, losses, regularization, schedules, updaters

__all__ = ["activations", "initializers", "losses", "regularization",
           "schedules", "updaters"]
