"""Round-1 VERDICT next #8: loud op registry, idempotent multihost
init, strict forge manifests."""

import os
import subprocess
import sys

import pytest


class TestRegistryLoudness:
    def test_all_families_registered(self):
        from veles_tpu.ops.registry import forward_registry
        for name in ("all2all", "all2all_tanh", "all2all_relu",
                     "softmax", "conv", "conv_tanh", "conv_relu",
                     "max_pooling", "avg_pooling", "stochastic_pooling",
                     "activation_tanh", "activation_relu",
                     "activation_sigmoid", "activation_log",
                     "activation_strict_relu", "dropout", "norm",
                     "deconv", "depooling"):
            assert name in forward_registry, name

    def test_broken_family_import_fails_loudly(self):
        """A transitive ImportError inside an op family must fail AT
        REGISTRY IMPORT with the family named, not surface later as
        'unknown layer type' (round-1 VERDICT weak #5)."""
        code = r"""
import importlib.abc
import sys

class Block(importlib.abc.MetaPathFinder):
    def find_spec(self, name, path, target=None):
        if name == "veles_tpu.ops.lrn":
            raise ImportError("synthetic lrn breakage")

sys.meta_path.insert(0, Block())
try:
    import veles_tpu.ops.registry  # noqa
except ImportError as e:
    assert "lrn" in str(e) and "registry" in str(e) or \
        "silently missing" in str(e), str(e)
    print("LOUD_FAILURE_OK")
else:
    print("IMPORTED_SILENTLY")
"""
        r = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, timeout=120,
                           cwd="/root/repo")
        assert "LOUD_FAILURE_OK" in r.stdout, (r.stdout, r.stderr)


class TestMultihostGuard:
    def test_initialize_called_once(self, monkeypatch):
        import jax

        from veles_tpu import launcher
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda *a, **k: calls.append(1))
        monkeypatch.setattr(launcher, "_multihost_initialized", False)
        launcher.init_multihost()
        launcher.init_multihost()
        assert calls == [1]

    def test_no_backend_touch_before_initialize(self, monkeypatch):
        """Round-2 advisor high: jax.process_count() initializes the
        XLA backend, after which distributed.initialize() always
        raises.  init_multihost must never call it (or jax.devices)
        before initialize."""
        import jax

        from veles_tpu import launcher

        def boom(*a, **k):
            raise AssertionError("backend touched before initialize")
        monkeypatch.setattr(jax, "process_count", boom)
        monkeypatch.setattr(jax, "devices", boom)
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda *a, **k: calls.append(1))
        monkeypatch.setattr(launcher, "_multihost_initialized", False)
        launcher.init_multihost()
        assert calls == [1]

    def test_already_initialized_client_detected(self, monkeypatch):
        """When the distributed client already exists, initialize()
        must not be called again."""
        from jax._src import distributed

        from veles_tpu import launcher
        monkeypatch.setattr(distributed.global_state, "client",
                            object(), raising=False)
        calls = []
        import jax
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda *a, **k: calls.append(1))
        monkeypatch.setattr(launcher, "_multihost_initialized", False)
        launcher.init_multihost()
        assert calls == []

    def test_refused_initialize_fails_loudly(self, monkeypatch):
        """A RuntimeError from initialize (backend already up) on a
        --multihost launch must FAIL LOUDLY (a silent single-process
        continuation would train 1/N of the data and checkpoint a
        state no peer can join), journal ``multihost.init_refused``,
        and continue solo only under VELES_MULTIHOST_ALLOW_SOLO=1."""
        import jax

        from veles_tpu import launcher, telemetry

        def refuse(*a, **k):
            raise RuntimeError("must be called before any JAX calls")
        monkeypatch.setattr(jax.distributed, "initialize", refuse)
        monkeypatch.setattr(launcher, "_multihost_initialized", False)
        monkeypatch.delenv("VELES_MULTIHOST_ALLOW_SOLO", raising=False)
        with pytest.raises(RuntimeError,
                           match="VELES_MULTIHOST_ALLOW_SOLO"):
            launcher.init_multihost()
        assert telemetry.recent_events("multihost.init_refused")
        # the explicit opt-in keeps the old continue-solo semantics
        monkeypatch.setenv("VELES_MULTIHOST_ALLOW_SOLO", "1")
        monkeypatch.setattr(launcher, "_multihost_initialized", False)
        launcher.init_multihost()  # must not raise
        assert launcher._multihost_initialized


class TestForgeStrictManifest:
    def test_unmanifested_member_rejected(self, tmp_path):
        """An archive member missing from the manifest's sha256 map
        must abort the install (smuggled unverified code)."""
        import io
        import tarfile

        from veles_tpu.forge import ForgePackage

        wf = tmp_path / "wf.py"
        wf.write_text("def run(launcher):\n    pass\n")
        out = str(tmp_path / "pkg.vpkg")
        ForgePackage.pack(out, "demo", str(wf), [], author="t")

        # append a file that the manifest does not cover
        evil = str(tmp_path / "evil.vpkg")
        with tarfile.open(out, "r:gz") as src, \
                tarfile.open(evil, "w:gz") as dst:
            for m in src.getmembers():
                dst.addfile(m, src.extractfile(m))
            payload = b"import os\n"
            info = tarfile.TarInfo("smuggled.py")
            info.size = len(payload)
            dst.addfile(info, io.BytesIO(payload))

        with pytest.raises(ValueError, match="not listed in the "
                                             "manifest"):
            ForgePackage.install(evil, str(tmp_path / "store"))


class TestForgeMarketplace:
    def test_publish_list_fetch_install_roundtrip(self, tmp_path):
        """The HTTP marketplace (reference: VelesForge upload/download)
        round-trips a package: publish -> list -> fetch -> install."""
        import threading

        from veles_tpu import forge

        wf = tmp_path / "wf.py"
        wf.write_text("def run(launcher):\n    pass\n")
        cfg = tmp_path / "cfg.py"
        cfg.write_text("root.demo.n = 1\n")
        pkg = str(tmp_path / "demo.vpkg")
        forge.ForgePackage.pack(pkg, "demo", str(wf), [str(cfg)],
                                version="1.2.0", author="t")

        server = forge.make_forge_server(str(tmp_path / "store"),
                                         port=0, host="127.0.0.1")
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        url = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            m = forge.publish(pkg, url)
            assert m["name"] == "demo" and m["file"] == "demo.vpkg"
            got = forge.fetch("demo", url, str(tmp_path / "dl"))
            inst = forge.ForgePackage.install(
                got, str(tmp_path / "inst"))
            assert inst["version"] == "1.2.0"
            import os
            assert os.path.isfile(os.path.join(inst["root"], "wf.py"))
            with pytest.raises(FileNotFoundError, match="available"):
                forge.fetch("nope", url)
        finally:
            server.shutdown()
            t.join(timeout=5)

    def test_fetch_rejects_malicious_listing_filename(self, tmp_path,
                                                      monkeypatch):
        """A compromised server's listing can claim "file":
        "../../x.vpkg" — fetch() must refuse before any path is built
        (round-3 ADVICE medium: arbitrary-path write on the client)."""
        import io
        import json as _json

        from veles_tpu import forge

        listing = _json.dumps([{"name": "demo", "version": "1.0.0",
                                "file": "../../escape.vpkg"}]).encode()

        class _Resp(io.BytesIO):
            def __enter__(self):
                return self

            def __exit__(self, *a):
                return False

        def fake_urlopen(url, timeout=None):
            assert url.endswith("/forge/list"), \
                "fetch must not request a package with an unsafe name"
            return _Resp(listing)

        monkeypatch.setattr("urllib.request.urlopen", fake_urlopen)
        with pytest.raises(ValueError, match="bad package file name"):
            forge.fetch("demo", "http://evil:1", str(tmp_path / "dl"))
        assert not (tmp_path.parent / "escape.vpkg").exists()

    def test_store_listing_survives_bad_manifest_member(self, tmp_path):
        """A crafted archive whose manifest.json member is a directory
        must not crash list_store for everyone (round-3 ADVICE low)."""
        import tarfile

        from veles_tpu import forge

        store = tmp_path / "store"
        store.mkdir()
        wf = tmp_path / "wf.py"
        wf.write_text("def run(launcher):\n    pass\n")
        good = str(store / "good.vpkg")
        forge.ForgePackage.pack(good, "good", str(wf), [])
        bad = str(store / "bad.vpkg")
        with tarfile.open(bad, "w:gz") as tar:
            info = tarfile.TarInfo("manifest.json")
            info.type = tarfile.DIRTYPE
            tar.addfile(info)
        listed = forge.ForgePackage.list_store(str(store))
        assert [m["name"] for m in listed] == ["good"]

    def test_server_defaults_to_loopback(self, tmp_path):
        """The unauthenticated upload endpoint must not bind all
        interfaces unless explicitly asked (round-3 ADVICE low)."""
        from veles_tpu import forge

        server = forge.make_forge_server(str(tmp_path / "store"), port=0)
        try:
            assert server.server_address[0] == "127.0.0.1"
        finally:
            server.server_close()

    def test_upload_rejects_garbage_and_bad_names(self, tmp_path):
        import threading
        from urllib.request import Request, urlopen
        from urllib.error import HTTPError

        from veles_tpu import forge

        server = forge.make_forge_server(str(tmp_path / "store"),
                                         port=0, host="127.0.0.1")
        t = threading.Thread(target=server.serve_forever, daemon=True)
        t.start()
        url = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            for path in ("/forge/upload/../../etc.vpkg",
                         "/forge/upload/notatar.vpkg",
                         "/forge/upload/wrongext.txt"):
                req = Request(url + path, data=b"not a tarball")
                with pytest.raises(HTTPError):
                    urlopen(req, timeout=10)
            store = tmp_path / "store"
            assert not any(os.scandir(store)), \
                "rejected uploads must leave nothing in the store"
        finally:
            server.shutdown()
            t.join(timeout=5)


class _ConfigUpdates:
    """Records jax.config.update calls instead of applying them, so a
    test can say what the program WOULD set without touching this
    process's real cache setting (or depending on an inherited one)."""

    def __init__(self, monkeypatch):
        import jax
        self.calls = []
        monkeypatch.setattr(
            jax.config, "update",
            lambda name, value: self.calls.append((name, value)))

    @property
    def cache_dirs(self):
        return [v for n, v in self.calls
                if n == "jax_compilation_cache_dir"]


class TestCompileCachePlacement:
    """PR 21: the compile cache can be placed from outside.  With
    JAX_COMPILATION_CACHE_DIR set the program keeps its cache there
    and makes NO jax_compilation_cache_dir update of its own; unset,
    a TPU engine uses the one fixed in-checkout directory and an
    XLA:CPU engine none."""

    REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def test_external_dir_wins_and_program_sets_nothing(
            self, monkeypatch, tmp_path):
        from veles_tpu import backends
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        updates = _ConfigUpdates(monkeypatch)
        for platform in ("tpu", "cpu"):
            backends._enable_persistent_compile_cache(platform)
            assert backends.compile_cache_dir(platform) == str(tmp_path)
        backends.JaxDevice(platform="cpu")   # the real call site
        assert updates.calls == []

    def test_unset_means_the_fixed_in_checkout_dir(self, monkeypatch):
        from veles_tpu import backends
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = _ConfigUpdates(monkeypatch)
        backends._enable_persistent_compile_cache("tpu")
        want = os.path.join(self.REPO, ".jax_cache")
        assert updates.cache_dirs == [want]
        assert backends.compile_cache_dir("tpu") == want
        # nothing that varies between runs or installs is in the path
        import jax
        assert jax.__version__ not in os.path.relpath(want, self.REPO)

    def test_cpu_engine_leaves_the_in_checkout_cache_off(
            self, monkeypatch):
        from veles_tpu import backends
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        updates = _ConfigUpdates(monkeypatch)
        backends.JaxDevice(platform="cpu")
        assert updates.calls == []
        assert backends.compile_cache_dir("cpu") is None


class TestDeviceRequestCannotHide:
    """PR 21: a request for the chip is the chip or an exception, and
    `auto` no longer turns a backend error into the numpy engine."""

    def test_tpu_raises_on_a_cpu_only_process(self):
        from veles_tpu.backends import make_device
        with pytest.raises(RuntimeError, match="(?i)tpu"):
            make_device("tpu")

    def test_auto_propagates_a_backend_error(self, monkeypatch):
        import jax

        from veles_tpu.backends import make_device

        def boom(*a, **k):
            raise RuntimeError("backend failed to initialize")

        make_device.cache_clear()
        monkeypatch.setattr(jax, "local_devices", boom)
        try:
            with pytest.raises(RuntimeError, match="failed to init"):
                make_device("auto")
        finally:
            make_device.cache_clear()

    def test_auto_announces_the_platform_it_resolved(self):
        from veles_tpu.backends import make_device
        d = make_device("auto").describe()
        assert d["platform"] == "cpu" and d["device_kind"]
        assert d["jax"] and d["jaxlib"] and d["libtpu"]

    def test_jax_is_no_longer_a_backend_name(self):
        from veles_tpu.backends import make_device
        with pytest.raises(ValueError):
            make_device("jax")

    def test_tpu_without_reported_memory_limit_is_an_error(self):
        from veles_tpu.backends import device_bytes_limit

        class Dev:
            def __init__(self, platform, stats):
                self.platform, self._stats = platform, stats

            def memory_stats(self):
                return self._stats

        assert device_bytes_limit(Dev("cpu", None)) is None
        assert device_bytes_limit(
            Dev("tpu", {"bytes_limit": 17})) == 17
        with pytest.raises(RuntimeError, match="bytes_limit"):
            device_bytes_limit(Dev("tpu", {}))
