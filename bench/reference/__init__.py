"""Plain references: the yardsticks ``correct`` is decided by.

A configuration file names its serving reference under ``"reference"``;
``bench/drivers/serve.py`` loads ``bench/reference/<name>.py`` and reaches
the architecture through it alone.  Such a module exposes:

- ``dims(conf) -> dict``: the sizes it needs, read from the config file;
- ``leaves(dims) -> [(name, shape, stacked)]``: every leaf of the
  program's parameter tree, in any number of segments (``segments/0``,
  ``segments/1``, ...); a stacked leaf's first axis is the layer, and
  further leading axes (experts) may follow it.  ``bench/weights.layout``
  decides from these alone which leaves the planner deploys and their grid;
- ``Weights(dims, seed)``: the seeded weights, made one slice at a time;
- ``served_gaps(weights, seqs, pad_to, *, control=None)``: the gap of every
  served token under the reference (or, with ``control``, of the token
  the lower-precision reference puts first).

The operation count of the same architecture is ``bench/roofline/<name>.py``
under the same name.  A reference imports nothing of the program.
"""
