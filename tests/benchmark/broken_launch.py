"""The benchmark's launcher with the timed path broken underneath: every
token the engine's step hands to the scheduler is altered where it is
produced (the engine's own state keeps the true one). A run driven
through this must come out with ``correct`` false."""

import sys

from benchmark import launch


def main(argv=None) -> int:
    from dstack_tpu.serve import engine

    step = engine.InferenceEngine.step

    def altered(self):
        def bump(tok):
            return [bump(t) for t in tok] if isinstance(tok, (list, tuple)) else int(tok) + 1

        return {slot: bump(tok) for slot, tok in step(self).items()}

    engine.InferenceEngine.step = altered
    return launch.main(argv)


if __name__ == "__main__":
    sys.exit(main())
