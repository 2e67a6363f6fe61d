"""Native runtime under test: the pytest suite configures + builds
native/ (cmake + ninja) and runs its ctest suite — the repo's L0 role
(SURVEY.md §1) — so Python CI goes red if the C++ registry, plugins, the
plugin=tpu embedded-CPython bridge, or the benchmark tools stop
compiling, and the bridge's multithreaded GIL discipline is exercised
on every run (native/tools/test_bridge_mt.cc; ctest TIMEOUT turns a
GIL deadlock into a failure)."""

import os
import shutil
import subprocess

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NATIVE = os.path.join(ROOT, "native")
BUILD = os.path.join(NATIVE, "build")


def _run(cmd, **kw):
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=600, **kw)


@pytest.fixture(scope="module")
def native_build():
    if shutil.which("cmake") is None or shutil.which("ninja") is None:
        pytest.skip("cmake/ninja not available")
    r = _run(["cmake", "-S", NATIVE, "-B", BUILD, "-G", "Ninja"])
    assert r.returncode == 0, f"cmake configure failed:\n{r.stdout}\n{r.stderr}"
    r = _run(["ninja", "-C", BUILD])
    assert r.returncode == 0, f"native build failed:\n{r.stdout}\n{r.stderr}"
    return BUILD


def test_native_builds(native_build):
    for target in ("libceph_tpu_ec.so", "libec_rs.so", "libec_tpu.so",
                   "ceph_erasure_code_benchmark", "ceph_erasure_code",
                   "test_bridge_mt"):
        assert os.path.exists(os.path.join(native_build, target)), target


def test_native_ctest(native_build):
    """roundtrip_rs + roundtrip_example + bridge_multithreaded (the
    plugin=tpu dlopen story end-to-end, from three threads)."""
    env = dict(os.environ, CEPH_TPU_JAX_PLATFORM="cpu")
    # the bridge embeds its own interpreter; don't leak the test
    # process's XLA device-count flags into it
    env.pop("XLA_FLAGS", None)
    r = _run(["ctest", "--output-on-failure"], cwd=native_build, env=env)
    assert r.returncode == 0, f"ctest failed:\n{r.stdout}\n{r.stderr}"


SAN_BUILD = os.path.join(NATIVE, "build-san")


@pytest.mark.slow
def test_native_ctest_under_sanitizers():
    """WITH_SANITIZERS=ON build (the reference's WITH_ASAN/WITH_UBSAN QA
    gate): the AVX2 gf8 region kernels, the plugin registry's dlopen
    path and the benchmark tool run their roundtrips under ASan+UBSan.
    The embedded-CPython bridge test is excluded — an ASan runtime
    inside a dlopen'd interpreter needs LD_PRELOAD gymnastics that
    belong in a dedicated harness, and the bridge's native surface
    (registry + kernels) is already covered by the included tests."""
    if shutil.which("cmake") is None or shutil.which("ninja") is None:
        pytest.skip("cmake/ninja not available")
    r = _run(["cmake", "-S", NATIVE, "-B", SAN_BUILD, "-G", "Ninja",
              "-DWITH_SANITIZERS=ON"])
    if r.returncode != 0:
        pytest.skip(f"sanitizer configure unsupported:\n{r.stderr}")
    r = _run(["ninja", "-C", SAN_BUILD])
    if r.returncode != 0 and "-fsanitize" in (r.stdout + r.stderr):
        pytest.skip("toolchain lacks asan/ubsan runtime")
    assert r.returncode == 0, \
        f"sanitizer build failed:\n{r.stdout}\n{r.stderr}"
    env = dict(os.environ,
               # the registry keeps plugin dlopen handles for the
               # process lifetime by design; LSan would report those
               # one-shot CLI allocations as leaks
               ASAN_OPTIONS="detect_leaks=0",
               UBSAN_OPTIONS="print_stacktrace=1")
    r = _run(["ctest", "--output-on-failure", "-R", "roundtrip"],
             cwd=SAN_BUILD, env=env)
    assert r.returncode == 0, \
        f"ctest under sanitizers failed:\n{r.stdout}\n{r.stderr}"


CORPUS = os.path.join(ROOT, "tests", "corpus")

# corpus profiles the native AVX2 RS plugin supports (reed_sol_van,
# w=8) — including the k=8,m=3 north-star shape
RS_CORPUS = [
    ("jerasure__k=4__m=2__technique=reed_sol_van",
     ["-P", "k=4", "-P", "m=2"]),
    ("jerasure__k=8__m=3__technique=reed_sol_van",
     ["-P", "k=8", "-P", "m=3"]),
]


def _encode_cli(native_build, plugin, params, content, outdir, env=None):
    exe = os.path.join(native_build, "ceph_erasure_code")
    r = _run([exe, "encode", "--plugin", plugin, *params,
              "--input", content, "--output-dir", str(outdir),
              "-d", native_build], env=env)
    assert r.returncode == 0, f"native encode failed:\n{r.stdout}\n{r.stderr}"


@pytest.mark.parametrize("cdir,params", RS_CORPUS)
def test_rs_chunks_byte_identical_to_python_corpus(native_build, tmp_path,
                                                   cdir, params):
    """Cross-implementation parity KAT (VERDICT r03 Next#2): the native
    C++ AVX2 RS plugin (native/src/gf8.cc pshufb split tables) and the
    Python/XLA jerasure path (ceph_tpu/matrices + region ops, pinned in
    tests/corpus) are two independently-written GF(2^8) Reed-Solomon
    implementations.  Their encoded chunks must agree byte-for-byte on
    the committed corpus payloads — mutual validation that neither side
    currently gets for free."""
    src = os.path.join(CORPUS, cdir)
    _encode_cli(native_build, "rs", params,
                os.path.join(src, "content"), tmp_path)
    k_m = sum(int(p.split("=")[1]) for p in params[1::2])
    for i in range(k_m):
        native_chunk = os.path.join(tmp_path, f"chunk.{i}")
        corpus_chunk = os.path.join(src, str(i))
        assert os.path.exists(native_chunk), f"chunk {i} not written"
        with open(native_chunk, "rb") as f:
            nb = f.read()
        with open(corpus_chunk, "rb") as f:
            cb = f.read()
        assert nb == cb, (f"{cdir} chunk {i}: native C++ differs from "
                          f"Python corpus ({len(nb)} vs {len(cb)} bytes)")


def test_rs_decode_reconstructs_corpus_content(native_build, tmp_path):
    """Native decode from a k-subset of the corpus chunks reproduces the
    original payload — the C++ inverse path against Python-encoded
    parity."""
    src = os.path.join(CORPUS, "jerasure__k=8__m=3__technique=reed_sol_van")
    import json as _json
    with open(os.path.join(src, "manifest.json")) as f:
        manifest = _json.load(f)
    # stage survivors only (drop chunk 0: data, chunk 9: parity — the
    # reconstruct-data and re-encode-parity branches both run)
    for i in range(11):
        if i in (0, 9):
            continue
        with open(os.path.join(src, str(i)), "rb") as f:
            data = f.read()
        with open(os.path.join(tmp_path, f"chunk.{i}"), "wb") as f:
            f.write(data)
    exe = os.path.join(native_build, "ceph_erasure_code")
    out = os.path.join(tmp_path, "restored")
    r = _run([exe, "decode", "--plugin", "rs", "-P", "k=8", "-P", "m=3",
              "--input-dir", str(tmp_path), "--output", out,
              "--size", str(manifest["size"]), "-d", native_build])
    assert r.returncode == 0, f"native decode failed:\n{r.stdout}\n{r.stderr}"
    with open(out, "rb") as f:
        restored = f.read()
    with open(os.path.join(src, "content"), "rb") as f:
        content = f.read()
    assert restored == content


TPU_BRIDGE_CORPUS = [
    ("jerasure__k=4__m=2__technique=reed_sol_van", 6,
     ["-P", "backend=jerasure", "-P", "technique=reed_sol_van",
      "-P", "k=4", "-P", "m=2"]),
    ("shec__c=2__k=6__m=3", 9,
     ["-P", "backend=shec", "-P", "k=6", "-P", "m=3", "-P", "c=2"]),
]


@pytest.mark.parametrize("cdir,nchunks,params", TPU_BRIDGE_CORPUS)
def test_tpu_bridge_chunks_match_corpus(native_build, tmp_path, cdir,
                                        nchunks, params):
    """plugin=tpu (the embedded-CPython bridge) must produce the exact
    corpus bytes through the dlopen ABI — pinning the bridge's buffer
    handoff and padding discipline, not just its liveness."""
    env = dict(os.environ, CEPH_TPU_JAX_PLATFORM="cpu")
    env.pop("XLA_FLAGS", None)
    src = os.path.join(CORPUS, cdir)
    _encode_cli(native_build, "tpu", params,
                os.path.join(src, "content"), tmp_path, env=env)
    for i in range(nchunks):
        with open(os.path.join(tmp_path, f"chunk.{i}"), "rb") as f:
            nb = f.read()
        with open(os.path.join(src, str(i)), "rb") as f:
            cb = f.read()
        assert nb == cb, f"{cdir} chunk {i}: bridge differs from corpus"


def test_tpu_bridge_pyroot_with_quotes_and_spaces(native_build, tmp_path):
    """The embedded-interpreter bootstrap must survive a CEPH_TPU_PYROOT
    containing quotes and spaces — values travel through the C API as
    objects, never interpolated into python source."""
    weird = tmp_path / "py root's \" \\ ~ dir"
    weird.mkdir()
    os.symlink(os.path.join(ROOT, "ceph_tpu"), weird / "ceph_tpu")
    env = dict(os.environ, CEPH_TPU_JAX_PLATFORM="cpu",
               CEPH_TPU_PYROOT=str(weird))
    env.pop("XLA_FLAGS", None)
    src = os.path.join(CORPUS, "jerasure__k=4__m=2__technique=reed_sol_van")
    _encode_cli(native_build, "tpu",
                ["-P", "backend=jerasure", "-P", "technique=reed_sol_van",
                 "-P", "k=4", "-P", "m=2"],
                os.path.join(src, "content"), tmp_path, env=env)
    with open(os.path.join(tmp_path, "chunk.4"), "rb") as f:
        nb = f.read()
    with open(os.path.join(src, "4"), "rb") as f:
        assert nb == f.read()


EXHAUSTIVE_CORPUS = [
    # (corpus dir, n, k, tpu-bridge params, python plugin, python profile)
    ("jerasure__k=4__m=2__technique=reed_sol_van", 6, 4,
     ["-P", "backend=jerasure", "-P", "technique=reed_sol_van",
      "-P", "k=4", "-P", "m=2"],
     "jerasure", {"technique": "reed_sol_van", "k": "4", "m": "2"}),
    ("shec__c=2__k=6__m=3", 9, 6,
     ["-P", "backend=shec", "-P", "k=6", "-P", "m=3", "-P", "c=2"],
     "shec", {"k": "6", "m": "3", "c": "2"}),
]


@pytest.mark.slow
@pytest.mark.parametrize("cdir,n,k,params,pyplugin,pyprofile",
                         EXHAUSTIVE_CORPUS)
def test_tpu_bridge_exhaustive_erasures(native_build, tmp_path, cdir, n,
                                        k, params, pyplugin, pyprofile):
    """ceph_erasure_code_non_regression.cc -> --erasures-generation
    exhaustive, through the libec_tpu dlopen bridge (VERDICT r04
    Next#8): every 1- and 2-erasure pattern is decoded by the native
    side and byte-compared against the corpus payload (which the
    Python path produced), catching decode-matrix bugs like the one
    the round-4 parity pin found.  Patterns the code cannot decode
    (possible for shec) are skipped via the Python plugin's own
    minimum_to_decode, mirroring the reference's error-continue."""
    import itertools
    import json as _json

    from ceph_tpu.codes.registry import ErasureCodePluginRegistry

    ec = ErasureCodePluginRegistry.instance().factory(
        pyplugin, dict(pyprofile))
    src = os.path.join(CORPUS, cdir)
    with open(os.path.join(src, "manifest.json")) as f:
        size = _json.load(f)["size"]
    with open(os.path.join(src, "content"), "rb") as f:
        content = f.read()
    chunks = {}
    for i in range(n):
        with open(os.path.join(src, str(i)), "rb") as f:
            chunks[i] = f.read()
    env = dict(os.environ, CEPH_TPU_JAX_PLATFORM="cpu")
    env.pop("XLA_FLAGS", None)
    exe = os.path.join(native_build, "ceph_erasure_code")
    patterns = [frozenset(c) for e in (1, 2)
                for c in itertools.combinations(range(n), e)]
    ran = 0
    for pat in patterns:
        avail = set(range(n)) - pat
        try:
            ec.minimum_to_decode(set(range(k)), avail)
        except IOError:
            continue            # undecodable pattern: reference skips
        workdir = tmp_path / "-".join(str(i) for i in sorted(pat))
        workdir.mkdir()
        for i in avail:
            with open(workdir / f"chunk.{i}", "wb") as f:
                f.write(chunks[i])
        out = workdir / "restored"
        r = _run([exe, "decode", "--plugin", "tpu", *params,
                  "--input-dir", str(workdir), "--output", str(out),
                  "--size", str(size), "-d", native_build], env=env)
        assert r.returncode == 0, \
            f"{cdir} erasures {sorted(pat)}:\n{r.stdout}\n{r.stderr}"
        with open(out, "rb") as f:
            assert f.read() == content, f"{cdir} erasures {sorted(pat)}"
        ran += 1
    # the sweep must have actually exercised patterns (shec skips a
    # few, never most)
    assert ran >= len(patterns) * 2 // 3, (ran, len(patterns))
