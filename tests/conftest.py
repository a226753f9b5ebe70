import os
import sys

# Tests see the default single CPU device. Make sure src/ is importable.
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Run the CPU backend without fused multiply-add. XLA's CPU compiler
# contracts ``a*b + c*d`` into an FMA on whichever product its fusion order
# puts second, so two programs that compute the same arithmetic (a kernel
# and its reference, a batched solver and its scalar rows) can differ in
# the last ulp. Without FMA every operation rounds once and the bitwise
# contracts the tests pin are properties of the arithmetic alone.
if "--xla_cpu_max_isa" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " --xla_cpu_max_isa=AVX"
    ).strip()
