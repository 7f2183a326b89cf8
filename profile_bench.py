"""Profile the 1k-host 3-tier bench under --scheduler=tpu (CPU backend)."""
import cProfile, pstats, sys, os, io
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
os.environ["JAX_PLATFORMS"] = "cpu"
import bench
from shadow_tpu.core.manager import Manager

sched = sys.argv[1] if len(sys.argv) > 1 else "tpu"
# warmup run compiles jit caches
bench.run_once(bench.config3, sched)

manager = Manager(bench.config3(sched))
for h in manager.hosts:
    h.set_tracing(False)
pr = cProfile.Profile()
pr.enable()
manager.run()
pr.disable()
st = pstats.Stats(pr)
st.sort_stats("cumulative").print_stats(45)
st.sort_stats("tottime").print_stats(45)
