"""DecentLaM on TPU: a decentralized large-batch training framework in JAX.

See README.md / DESIGN.md.  Subpackages: ``core`` (the paper's algorithms),
``sim`` (discrete-event cluster simulator), ``models`` (manual-TP model
zoo), ``kernels`` (Pallas TPU kernels), ``train`` (distributed runtime),
``data``, ``launch``, ``configs``.
"""

__version__ = "1.0.0"
