"""Faults planted under the timed path: the control (``accept_all``) and
the faults a cell can have.  Neither the benchmark's runs nor the program
use this module; ``control.py`` reads the control on the chip and
``tests/test_faults.py`` sees each fault turn ``correct`` false."""

from contextlib import contextmanager

#: name -> what it breaks
FAULTS = {
    "accept_all": "the control: every verdict is True, the guarantee that "
                  "each verdict is the spec's is broken",
    "flip_answer": "an answer altered where it is produced: the device "
                   "verdict is negated",
    "half_batch": "half of the batch left out: only the first half of the "
                  "sets (blobs) reach the device program",
    "host_route": "the device dispatch fails, so the supervisor verifies on "
                  "the host golden model",
}


@contextmanager
def planted(name: str):
    from lighthouse_tpu import fault_injection
    from lighthouse_tpu.ops import kzg_device, pairing, verify

    if name not in FAULTS:
        raise KeyError(f"unknown fault {name!r}; known: {sorted(FAULTS)}")
    undo = []

    def patch(obj, attr, new):
        undo.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, new)

    if name == "accept_all":
        patch(pairing, "fe_is_one", lambda fe: True)
    elif name == "flip_answer":
        is_one = pairing.fe_is_one
        patch(pairing, "fe_is_one", lambda fe: not is_one(fe))
    elif name == "half_batch":
        build = verify.build_batch
        patch(verify, "build_batch", lambda sets, rands: build(
            sets[:max(1, len(sets) // 2)], rands[:max(1, len(sets) // 2)]))
        kzg = kzg_device.verify_kzg_proof_batch_device

        def half(c, p, r, z, y, tau, host_fn=None):
            h = max(1, len(c) // 2)
            return kzg(c[:h], p[:h], r[:h], z[:h], y[:h], tau, host_fn=host_fn)

        patch(kzg_device, "verify_kzg_proof_batch_device", half)
    elif name == "host_route":
        fault_injection.install("device.dispatch", mode="error")
    try:
        yield
    finally:
        for obj, attr, old in reversed(undo):
            setattr(obj, attr, old)
        if name == "host_route":
            fault_injection.clear("device.dispatch")
