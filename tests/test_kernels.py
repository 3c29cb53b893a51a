"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.rglru_scan import rglru_scan
from repro.kernels.ssd_scan import ssd_scan

KEY = jax.random.PRNGKey(0)


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 2e-5


def _mx(a, b):
    return float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape,window", [
    ((2, 256, 4, 2, 64), None),
    ((1, 512, 8, 8, 32), None),
    ((2, 256, 6, 2, 64), 128),
    ((1, 128, 2, 1, 64), None),
    ((1, 128, 4, 4, 128), 64),
])
def test_flash_attention(shape, window, dtype):
    B, S, H, KV, D = shape
    ks = jax.random.split(KEY, 3)
    q = (jax.random.normal(ks[0], (B, S, H, D)) * 0.3).astype(dtype)
    k = (jax.random.normal(ks[1], (B, S, KV, D)) * 0.3).astype(dtype)
    v = (jax.random.normal(ks[2], (B, S, KV, D)) * 0.3).astype(dtype)
    out = flash_attention(q, k, v, window=window, interpret=True,
                          block_q=128, block_k=128)
    exp = ref.flash_attention(q, k, v, window=window)
    assert out.shape == exp.shape and out.dtype == dtype
    assert _mx(out, exp) < _tol(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KV,D", [(2, 512, 4, 2, 64), (1, 1024, 8, 8, 32),
                                        (2, 256, 2, 1, 128)])
def test_decode_attention(B, S, H, KV, D, dtype):
    ks = jax.random.split(KEY, 3)
    q = (jax.random.normal(ks[0], (B, 1, H, D)) * 0.3).astype(dtype)
    kc = (jax.random.normal(ks[1], (B, S, KV, D)) * 0.3).astype(dtype)
    vc = (jax.random.normal(ks[2], (B, S, KV, D)) * 0.3).astype(dtype)
    lens = jnp.full((B,), S // 2, jnp.int32)
    out = decode_attention(q, kc, vc, lens, interpret=True, block_s=128)
    exp = ref.decode_attention(q, kc, vc, lens)
    assert _mx(out, exp) < _tol(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("T,D,F,E", [(512, 64, 128, 4), (1024, 128, 64, 8),
                                     (256, 256, 256, 2)])
def test_grouped_matmul(T, D, F, E, dtype):
    ks = jax.random.split(KEY, 2)
    x = (jax.random.normal(ks[0], (T, D)) * 0.3).astype(dtype)
    w = (jax.random.normal(ks[1], (E, D, F)) * 0.3).astype(dtype)
    sizes = jax.random.randint(jax.random.PRNGKey(7), (E,), 0, 2 * T // E)
    sizes = sizes.at[-1].add(T - sizes.sum())
    out = grouped_matmul(x, w, sizes, interpret=True, block_t=128)
    exp = ref.grouped_matmul(x, w, sizes)
    assert _mx(out, exp) < _tol(dtype)


def test_grouped_matmul_empty_groups():
    x = jnp.ones((128, 32), jnp.float32)
    w = jnp.ones((4, 32, 16), jnp.float32)
    sizes = jnp.array([0, 128, 0, 0], jnp.int32)
    out = grouped_matmul(x, w, sizes, interpret=True, block_t=64)
    exp = ref.grouped_matmul(x, w, sizes)
    assert _mx(out, exp) < 1e-5


@pytest.mark.parametrize("B,S,H,P,N,Q", [(2, 256, 4, 32, 16, 64),
                                         (1, 128, 2, 64, 32, 32),
                                         (2, 64, 8, 16, 8, 16)])
def test_ssd_scan(B, S, H, P, N, Q):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y1, f1 = ssd_scan(x, dt, A, Bm, Cm, chunk=Q, interpret=True)
    y2, f2 = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=Q)
    assert _mx(y1, y2) < 1e-3 and _mx(f1, f2) < 1e-3


def test_ssd_chunk_invariance():
    """Oracle: result independent of chunk size (the SSD identity)."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 1, 128, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y16, f16 = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    y64, f64 = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=64)
    assert _mx(y16, y64) < 1e-4 and _mx(f16, f64) < 1e-4


def test_ssd_matches_sequential_recurrence():
    """Oracle vs literal h_t = exp(dt A) h + dt B x recurrence."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 1, 32, 2, 8, 4
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y, fin = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=8)
    state = jnp.zeros((B, H, P, N))
    ys = []
    for t in range(S):
        yt, state = ref.ssd_decode_step(x[:, t], dt[:, t], A, Bm[:, t],
                                        Cm[:, t], state)
        ys.append(yt)
    y_seq = jnp.stack(ys, axis=1)
    assert _mx(y, y_seq) < 1e-4 and _mx(fin, state) < 1e-4


@pytest.mark.parametrize("B,S,W", [(2, 256, 128), (1, 128, 64), (2, 64, 256)])
def test_rglru_scan(B, S, W):
    ks = jax.random.split(KEY, 3)
    x = jax.random.normal(ks[0], (B, S, W), jnp.float32) * 0.5
    ig = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, W)))
    ag = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, W)))
    la = -jax.nn.softplus(-jnp.linspace(2, 6, W))
    h1, f1 = rglru_scan(x, ig, ag, la, interpret=True, block_s=64)
    h2, f2 = ref.rglru_scan(x, ig, ag, la)
    assert _mx(h1, h2) < 1e-4 and _mx(f1, f2) < 1e-4


def test_rglru_matches_sequential():
    ks = jax.random.split(KEY, 3)
    B, S, W = 1, 48, 32
    x = jax.random.normal(ks[0], (B, S, W), jnp.float32) * 0.5
    ig = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, W)))
    ag = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, W)))
    la = -jax.nn.softplus(-jnp.linspace(2, 6, W))
    h, fin = ref.rglru_scan(x, ig, ag, la)
    state = jnp.zeros((B, W))
    for t in range(S):
        ht, state = ref.rglru_decode_step(x[:, t], ig[:, t], ag[:, t], la, state)
    assert _mx(fin, state) < 1e-4
    assert _mx(h[:, -1], state) < 1e-4


def test_flash_chunk_composability():
    """flash over [k1;k2] == chunked flash_chunk(k1) then (k2)."""
    ks = jax.random.split(KEY, 3)
    B, S, H, KV, D = 1, 128, 4, 2, 32
    q = jax.random.normal(ks[0], (B, 64, H, D)) * 0.3
    k = jax.random.normal(ks[1], (B, S, KV, D)) * 0.3
    v = jax.random.normal(ks[2], (B, S, KV, D)) * 0.3
    full = ref.flash_attention(q, k, v, causal=False)
    c = ref.flash_chunk(q, k[:, :64], v[:, :64], causal=False, k_offset=0)
    c = ref.flash_chunk(q, k[:, 64:], v[:, 64:], c, causal=False, k_offset=64)
    out = ref.flash_finalize(c[0], c[2], q.dtype)
    assert _mx(full, out) < 1e-5


@pytest.mark.parametrize("window", [1, 100, 300])
def test_decode_attention_window(window):
    """Sliding-window decode in the kernel (keys below length - window
    masked) against the oracle, lengths straddling block edges."""
    ks = jax.random.split(KEY, 3)
    B, S, H, KV, D = 3, 512, 4, 2, 64
    q = jax.random.normal(ks[0], (B, 1, H, D)) * 0.3
    kc = jax.random.normal(ks[1], (B, S, KV, D)) * 0.3
    vc = jax.random.normal(ks[2], (B, S, KV, D)) * 0.3
    lens = jnp.asarray([512, 130, 257], jnp.int32)
    out = decode_attention(q, kc, vc, lens, window=window, interpret=True,
                           block_s=128)
    exp = ref.decode_attention(q, kc, vc, lens, window=window)
    assert _mx(out, exp) < 2e-5


def test_flash_attention_traced_row_offsets():
    """Per-row traced q_offset under vmap + jit (the composed prefill's
    flash_rows) matches the oracle row by row."""
    ks = jax.random.split(KEY, 3)
    P, C, S, H, KV, D = 3, 64, 256, 4, 2, 32
    q = jax.random.normal(ks[0], (P, C, H, D)) * 0.3
    k = jax.random.normal(ks[1], (P, S, KV, D)) * 0.3
    v = jax.random.normal(ks[2], (P, S, KV, D)) * 0.3
    starts = jnp.asarray([0, 64, 192], jnp.int32)

    @jax.jit
    def rows(q, k, v, starts):
        return jax.vmap(lambda a, b, c, o: flash_attention(
            a[None], b[None], c[None], q_offset=o, interpret=True,
            block_q=64, block_k=64)[0])(q, k, v, starts)

    out = rows(q, k, v, starts)
    for r in range(P):
        exp = ref.flash_attention(q[r:r + 1], k[r:r + 1], v[r:r + 1],
                                  q_offset=int(starts[r]))
        assert _mx(out[r:r + 1], exp) < 2e-5


@pytest.mark.parametrize("window", [None, 96])
def test_flash_attention_gradient(window):
    """custom_vjp: grads through the kernel equal the oracle's grads."""
    ks = jax.random.split(KEY, 4)
    B, S, H, KV, D = 1, 256, 4, 2, 32
    q = jax.random.normal(ks[0], (B, S, H, D)) * 0.3
    k = jax.random.normal(ks[1], (B, S, KV, D)) * 0.3
    v = jax.random.normal(ks[2], (B, S, KV, D)) * 0.3
    ct = jax.random.normal(ks[3], (B, S, H, D))

    def loss(f):
        return lambda q_, k_, v_: jnp.sum(f(q_, k_, v_) * ct)

    got = jax.grad(loss(lambda *a: flash_attention(
        *a, window=window, interpret=True, block_q=128, block_k=128)),
        (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda *a: ref.flash_attention(*a, window=window)),
                    (0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert _mx(g, w) < 1e-5


def test_ssd_scan_init_state():
    """Chunked prefill resumes from a carried state: scanning the second
    half from the first half's final state equals one whole scan."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 128, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    h = S // 2
    y_all, f_all = ref.ssd_scan(x, dt, A, Bm, Cm, chunk=32)
    _, f1 = ssd_scan(x[:, :h], dt[:, :h], A, Bm[:, :h], Cm[:, :h], chunk=32,
                     interpret=True)
    y2, f2 = ssd_scan(x[:, h:], dt[:, h:], A, Bm[:, h:], Cm[:, h:], chunk=32,
                      init_state=f1, interpret=True)
    assert _mx(y2, y_all[:, h:]) < 1e-3 and _mx(f2, f_all) < 1e-3


def test_rglru_scan_init_state():
    ks = jax.random.split(KEY, 3)
    B, S, W = 2, 128, 256
    x = jax.random.normal(ks[0], (B, S, W), jnp.float32) * 0.5
    ig = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, W)))
    ag = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, W)))
    la = -jax.nn.softplus(-jnp.linspace(2, 6, W))
    h_all, f_all = ref.rglru_scan(x, ig, ag, la)
    h = S // 2
    _, f1 = rglru_scan(x[:, :h], ig[:, :h], ag[:, :h], la, interpret=True,
                       block_s=32, block_w=128)
    h2, f2 = rglru_scan(x[:, h:], ig[:, h:], ag[:, h:], la, init_state=f1,
                        interpret=True, block_s=32, block_w=128)
    assert _mx(h2, h_all[:, h:]) < 1e-4 and _mx(f2, f_all) < 1e-4


def test_grouped_matmul_f_tiles():
    """F split into several lane tiles (block_f < F)."""
    ks = jax.random.split(KEY, 2)
    T, D, F, E = 256, 64, 512, 4
    x = jax.random.normal(ks[0], (T, D)) * 0.3
    w = jax.random.normal(ks[1], (E, D, F)) * 0.3
    sizes = jnp.asarray([100, 0, 120, 36], jnp.int32)
    out = grouped_matmul(x, w, sizes, interpret=True, block_t=64,
                         block_f=128)
    assert _mx(out, ref.grouped_matmul(x, w, sizes)) < 2e-5


@pytest.mark.parametrize("Sq,Sk,causal,q_offset,window", [
    (45, 45, True, 0, None),           # no 16-row divisor: padded
    (300, 300, True, 0, 64),
    (40, 300, False, 0, None),         # padded keys must be masked
    (24, 300, True, 276, None),        # chunk at the end of a long prefix
])
def test_flash_attention_padded_lengths(Sq, Sk, causal, q_offset, window):
    ks = jax.random.split(KEY, 3)
    H, KV, D = 4, 2, 32
    q = jax.random.normal(ks[0], (1, Sq, H, D)) * 0.3
    k = jax.random.normal(ks[1], (1, Sk, KV, D)) * 0.3
    v = jax.random.normal(ks[2], (1, Sk, KV, D)) * 0.3
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_offset=q_offset, interpret=True, block_q=128,
                          block_k=128)
    exp = ref.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset)
    assert out.shape == exp.shape
    assert _mx(out, exp) < 2e-5


def test_decode_attention_padded_cache():
    """A cache length with no 8-row divisor below the block is padded."""
    ks = jax.random.split(KEY, 3)
    B, S, H, KV, D = 2, 513, 4, 2, 32
    q = jax.random.normal(ks[0], (B, 1, H, D)) * 0.3
    kc = jax.random.normal(ks[1], (B, S, KV, D)) * 0.3
    vc = jax.random.normal(ks[2], (B, S, KV, D)) * 0.3
    lens = jnp.asarray([513, 200], jnp.int32)
    out = decode_attention(q, kc, vc, lens, interpret=True, block_s=128)
    assert _mx(out, ref.decode_attention(q, kc, vc, lens)) < 2e-5


def test_ssd_scan_gradient():
    """Grads through the kernel (oracle VJP) equal the oracle's, init
    state included."""
    ks = jax.random.split(KEY, 7)
    B, S, H, P, N = 1, 64, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    s0 = jax.random.normal(ks[5], (B, H, P, N)) * 0.3
    ct = jax.random.normal(ks[6], (B, S, H, P))

    def loss(f):
        return lambda *a: jnp.sum(f(*a[:5], init_state=a[5])[0] * ct)

    args = (x, dt, A, Bm, Cm, s0)
    got = jax.grad(loss(lambda *a, **k: ssd_scan(*a, chunk=16, interpret=True,
                                                 **k)),
                   range(6))(*args)
    want = jax.grad(loss(lambda *a, **k: ref.ssd_scan(*a, chunk=16, **k)),
                    range(6))(*args)
    for g, w in zip(got, want):
        assert _mx(g, w) < 1e-5


def test_rglru_scan_gradient():
    ks = jax.random.split(KEY, 5)
    B, S, W = 1, 64, 128
    x = jax.random.normal(ks[0], (B, S, W)) * 0.5
    ig = jax.nn.sigmoid(jax.random.normal(ks[1], (B, S, W)))
    ag = jax.nn.sigmoid(jax.random.normal(ks[2], (B, S, W)))
    la = -jax.nn.softplus(-jnp.linspace(2, 6, W))
    h0 = jax.random.normal(ks[3], (B, W)) * 0.3
    ct = jax.random.normal(ks[4], (B, S, W))

    def loss(f):
        return lambda *a: jnp.sum(f(*a[:4], init_state=a[4])[0] * ct)

    args = (x, ig, ag, la, h0)
    got = jax.grad(loss(lambda *a, **k: rglru_scan(*a, interpret=True,
                                                   block_s=32, **k)),
                   range(5))(*args)
    want = jax.grad(loss(ref.rglru_scan), range(5))(*args)
    for g, w in zip(got, want):
        assert _mx(g, w) < 1e-5
