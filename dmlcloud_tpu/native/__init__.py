"""Native (C++) runtime components, loaded via ctypes with Python fallbacks.

The reference is pure Python and delegates native work to torch's C++
(SURVEY.md §2.1 language note). Here the host-side hot paths that torch used
to cover get their own small C++ library (``libdmltpu.so``, built by
``native/build.sh`` or ``python -m dmlcloud_tpu.native.build``):

- ``interleave``: parallel strided memcpy batch interleaving (the inner loop
  of ``data.interleave_batches``).
- ``pack``: the greedy sequence packer (``pack_sequences_fast`` /
  ``pack_flat``) — bit-identical to ``data.pack_sequences``, one memcpy
  pass instead of a per-document Python loop (tests/test_native.py holds
  the identity; its speed is not measured on the chip).

Every entry point degrades gracefully to Python/numpy when the library
isn't built.
"""

from . import interleave, pack

__all__ = ["interleave", "pack"]
