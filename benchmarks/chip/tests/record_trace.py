"""Record the small profiler trace ``testdata/`` holds for test_trace.py.

    python benchmarks/chip/tests/record_trace.py [OUT]   # on a TPU

Two host spans around a flash-attention forward and backward at a small
size and a plain matmul, under the profiler; the ``.xplane.pb`` is
copied to ``OUT`` (default ``testdata/small.xplane.pb``).
"""
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.ops import flash_attention_op

    if jax.devices()[0].platform != "tpu":
        print("record_trace.py: needs a TPU", file=sys.stderr)
        return 1
    T, H, D = 1024, 4, 128
    seg = jnp.asarray(np.repeat([1, 2], T // 2)[None], jnp.int32)
    pos = jnp.asarray(np.concatenate([np.arange(T // 2)] * 2)[None], jnp.int32)
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.normal(size=(1, H, T, D)), jnp.bfloat16) for _ in range(3))

    @jax.jit
    def attn_grad(q, k, v):
        f = lambda q, k, v: flash_attention_op(  # noqa: E731
            q, k, v, seg, seg, pos, pos, causal=True, block_q=256,
            block_kv=256).astype(jnp.float32).sum()
        return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

    mm = jax.jit(lambda a: a @ a)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready((attn_grad(q, k, v), mm(a)))
    out = Path(tempfile.mkdtemp())
    with jax.profiler.trace(str(out)):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("dispatch"):
                g = attn_grad(q, k, v)
            with jax.profiler.TraceAnnotation("wait_step"):
                jax.block_until_ready(g)
            with jax.profiler.TraceAnnotation("loader_wait"):
                jax.block_until_ready(mm(a))
    src = sorted(out.rglob("*.xplane.pb"))[-1]
    dst = Path(sys.argv[1]) if len(sys.argv) > 1 else HERE / "testdata" / "small.xplane.pb"
    dst.parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, dst)
    print(f"wrote {dst} ({dst.stat().st_size} bytes)")
    shutil.rmtree(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
