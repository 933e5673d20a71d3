"""Compile the main path's chip programs for a DESCRIBED TPU v5e — no chip.

The TPU compiler is installed in the sandbox and compiles for a topology
that is described and not attached (on-chip-measurement guide, section 2):
what Mosaic / XLA:TPU refuse here they refuse on the chip, at no chip
time.  Nothing runs, so these tests say nothing about results or times.

One file a program family, so that ``--dist loadfile`` hands the families
to different workers (one file held a worker for 1,075 s of tier-1's
1,219 s until PR 47).  Rules this package keeps:

- Every process that describes the topology loads libtpu, and a second
  one aborts on ``/tmp/libtpu_lockfile`` unless it finds
  ``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` in ITS environment.  A run with more
  than one worker needs the variable, as the driver's tier-1 command has
  it; no repo file sets it.
  Where the topology cannot be described - no compiler installed, or the
  lock held - the ``topo`` fixture skips, as it always has.
- The topology is described ONLY inside ``conftest.py``'s module-scoped
  ``topo`` fixture — never at import, in a skipif or in parametrize.
- No tier-1 file past ~300 s on the driver's run (ROADMAP.md D13): a new
  described compile goes in the family file with the most room.
- Code that asks ``jax.default_backend()`` sees the CPU here, so the tests
  steer it themselves (``interpret=False``, builders called directly).
"""
