"""Operation and byte counts: what a kernel or a served token needs.

Kernel counts (``cim_matmul``, ``hamming``) are named after the kernel
whose ``<kernel>_roofline`` metric reads them.  A serving configuration's
model count is named after its reference: ``bench/metrics/mfu.serve.py``
loads ``bench/roofline/<conf["reference"]>.py``, which exposes
``prefill_ops(dims, prompt)`` and ``decode_ops(dims, context)`` over the
reference's ``dims(conf)``.  A configuration of a new architecture adds
its count as a file of that name.
"""
