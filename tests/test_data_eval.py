"""Data/eval tests with synthetic on-disk datasets (no external downloads)."""

import os

import numpy as np
import pytest

from raft_tpu.data import (
    Sintel,
    FlyingChairs,
    Kitti,
    read_flo,
    read_flow_png,
    read_pfm,
    write_flo,
    write_flow_png,
)
from raft_tpu.eval import InputPadder, validate
from raft_tpu.models import RAFT_SMALL, build_raft, init_variables


def _write_png(path, arr):
    from PIL import Image

    Image.fromarray(arr).save(path)


def make_sintel(tmp_path, scenes=("alley_1",), frames=3, h=64, w=96):
    rng = np.random.default_rng(0)
    root = tmp_path / "sintel"
    for scene in scenes:
        for d in ("training/clean", "training/final", "training/flow"):
            os.makedirs(root / d / scene, exist_ok=True)
        for i in range(1, frames + 1):
            img = rng.integers(0, 255, (h, w, 3), dtype=np.uint8)
            _write_png(root / "training/clean" / scene / f"frame_{i:04d}.png", img)
            _write_png(root / "training/final" / scene / f"frame_{i:04d}.png", img)
            if i < frames:
                flow = rng.uniform(-3, 3, (h, w, 2)).astype(np.float32)
                write_flo(
                    str(root / "training/flow" / scene / f"frame_{i:04d}.flo"), flow
                )
    return str(root)


class TestIO:
    def test_flo_round_trip(self, tmp_path, rng):
        flow = rng.uniform(-100, 100, (17, 23, 2)).astype(np.float32)
        p = str(tmp_path / "x.flo")
        write_flo(p, flow)
        np.testing.assert_array_equal(read_flo(p), flow)

    def test_pfm_round_trip(self, tmp_path, rng):
        from raft_tpu.data.io import read_pfm, write_pfm

        from raft_tpu.data.io import read_flow

        flow = rng.uniform(-50, 50, (13, 17, 2)).astype(np.float32)
        p = str(tmp_path / "x.pfm")
        write_pfm(p, flow)
        back, valid = read_flow(p)
        np.testing.assert_array_equal(back, flow)
        assert valid is None
        gray = rng.uniform(0, 1, (9, 11)).astype(np.float32)
        write_pfm(str(tmp_path / "g.pfm"), gray)
        np.testing.assert_array_equal(read_pfm(str(tmp_path / "g.pfm")), gray)

    def test_flo_bad_magic(self, tmp_path):
        p = str(tmp_path / "bad.flo")
        with open(p, "wb") as f:
            f.write(b"\x00" * 16)
        with pytest.raises(ValueError, match="magic"):
            read_flo(p)

    def test_kitti_png_round_trip(self, tmp_path, rng):
        flow = (rng.uniform(-64, 64, (10, 12, 2)) * 64).round() / 64
        flow = flow.astype(np.float32)
        valid = rng.integers(0, 2, (10, 12)).astype(bool)
        p = str(tmp_path / "f.png")
        write_flow_png(p, flow, valid)
        rflow, rvalid = read_flow_png(p)
        np.testing.assert_allclose(rflow, flow, atol=1 / 64)
        np.testing.assert_array_equal(rvalid, valid)

    def test_pfm_reader(self, tmp_path, rng):
        data = rng.uniform(-5, 5, (6, 8, 3)).astype("<f4")
        p = str(tmp_path / "x.pfm")
        with open(p, "wb") as f:
            f.write(b"PF\n8 6\n-1.0\n")
            f.write(np.flipud(data).tobytes())
        out = read_pfm(p)
        np.testing.assert_allclose(out, data)


class TestDatasets:
    def test_sintel_enumeration(self, tmp_path):
        root = make_sintel(tmp_path, scenes=("alley_1", "ambush_2"), frames=4)
        ds = Sintel(root, dstype="clean")
        assert len(ds) == 6  # 3 pairs per scene x 2 scenes
        s = ds[0]
        assert s["image1"].shape == (64, 96, 3)
        assert s["flow"].shape == (64, 96, 2)
        assert s["valid"].all()

    def test_flying_chairs_split(self, tmp_path, rng):
        root = tmp_path / "chairs"
        os.makedirs(root / "data")
        labels = []
        for i in range(1, 5):
            img = rng.integers(0, 255, (32, 48, 3), dtype=np.uint8)
            from PIL import Image

            Image.fromarray(img).save(root / "data" / f"{i:05d}_img1.ppm")
            Image.fromarray(img).save(root / "data" / f"{i:05d}_img2.ppm")
            write_flo(
                str(root / "data" / f"{i:05d}_flow.flo"),
                rng.uniform(-2, 2, (32, 48, 2)).astype(np.float32),
            )
            labels.append(1 if i % 2 else 2)
        np.savetxt(root / "FlyingChairs_train_val.txt", labels, fmt="%d")
        assert len(FlyingChairs(str(root), split="train")) == 2
        assert len(FlyingChairs(str(root), split="val")) == 2

    def test_kitti_enumeration(self, tmp_path, rng):
        root = tmp_path / "kitti"
        os.makedirs(root / "training/image_2")
        os.makedirs(root / "training/flow_occ")
        for i in range(3):
            img = rng.integers(0, 255, (24, 32, 3), dtype=np.uint8)
            _write_png(root / "training/image_2" / f"{i:06d}_10.png", img)
            _write_png(root / "training/image_2" / f"{i:06d}_11.png", img)
            write_flow_png(
                str(root / "training/flow_occ" / f"{i:06d}_10.png"),
                rng.uniform(-10, 10, (24, 32, 2)).astype(np.float32),
                np.ones((24, 32), bool),
            )
        ds = Kitti(str(root))
        assert len(ds) == 3
        s = ds[0]
        assert s["flow"].shape == (24, 32, 2)


class TestPadder:
    @pytest.mark.parametrize("mode", ["sintel", "downstream"])
    def test_pad_unpad(self, rng, mode):
        img = rng.uniform(0, 1, (1, 436, 1024, 3)).astype(np.float32)
        padder = InputPadder(img.shape, mode=mode)
        padded = padder.pad(img)
        assert padded.shape[1] % 8 == 0 and padded.shape[2] % 8 == 0
        assert padded.shape[1] == 440
        np.testing.assert_array_equal(padder.unpad(padded), img)
        if mode == "sintel":
            assert padder.pads[0] == (2, 2)
        else:
            assert padder.pads[0] == (0, 4)

    def test_replicate_semantics(self):
        img = np.arange(12, dtype=np.float32).reshape(1, 2, 6, 1)
        padder = InputPadder(img.shape, mode="downstream")
        padded = padder.pad(img)
        # horizontal pad splits 1|1: interior preserved, edges replicated
        np.testing.assert_array_equal(padded[0, 0, 1:7, 0], img[0, 0, :, 0])
        assert padded[0, 0, 0, 0] == img[0, 0, 0, 0]
        assert padded[0, 0, -1, 0] == img[0, 0, -1, 0]
        # vertical pad all at the bottom: rows 2.. replicate the last row
        np.testing.assert_array_equal(padded[0, -1, 1:7, 0], img[0, -1, :, 0])


class TestValidate:
    def test_validate_on_synthetic_sintel(self, tmp_path):
        root = make_sintel(tmp_path, scenes=("alley_1",), frames=3, h=64, w=96)
        cfg = RAFT_SMALL.replace(
            feature_encoder_widths=(8, 8, 12, 16, 24),
            context_encoder_widths=(8, 8, 12, 16, 40),
            motion_corr_widths=(16,),
            motion_flow_widths=(16, 8),
            motion_out_channels=20,
            gru_hidden=24,
            flow_head_hidden=16,
        )
        # 64x96 is below the 128px 4-level pyramid minimum -> use 2 levels
        from raft_tpu.models.corr import CorrBlock

        cfg2 = cfg.replace(corr_levels=2)
        model = build_raft(cfg2, corr_block=CorrBlock(num_levels=2, radius=3))
        variables = init_variables(model)
        res = validate(model, variables, Sintel(root), num_flow_updates=2)
        for k in ("epe", "1px", "3px", "5px", "fps"):
            assert k in res
        assert np.isfinite(res["epe"]) and res["epe"] > 0

    def test_fps_chain_length_64_when_dataset_allows(self, tmp_path, monkeypatch):
        """The throughput chain must default to >= 64 pairs (bench.py's
        chain-length doctrine: a short chain leaks its one-time dispatch +
        fetch cost into the per-pair figure). The chain itself is monkeypatched out — this asserts the
        collection logic, not the timing."""
        import importlib

        # raft_tpu.eval re-exports the `validate` function under the same
        # name as the submodule, so `import ... as V` would bind the function
        V = importlib.import_module("raft_tpu.eval.validate")

        root = make_sintel(tmp_path, scenes=("alley_1",), frames=66, h=64, w=96)
        cfg = RAFT_SMALL.replace(
            feature_encoder_widths=(8, 8, 12, 16, 24),
            context_encoder_widths=(8, 8, 12, 16, 40),
            motion_corr_widths=(16,),
            motion_flow_widths=(16, 8),
            motion_out_channels=20,
            gru_hidden=24,
            flow_head_hidden=16,
            corr_levels=2,
        )
        from raft_tpu.models.corr import CorrBlock

        model = build_raft(cfg, corr_block=CorrBlock(num_levels=2, radius=3))
        variables = init_variables(model)

        seen = {}

        def fake_chain(model, variables, images1, images2, **kw):
            seen["n"] = images1.shape[0]
            return 1.0

        monkeypatch.setattr(V, "chained_pairs_per_s", fake_chain)
        res = V.validate(model, variables, Sintel(root), num_flow_updates=2)
        assert seen["n"] == 64
        assert res["fps"] == 1.0


class TestFlowEstimator:
    def test_owns_normalize_pad_contract(self, rng):
        """FlowEstimator: raw [0,255] uint8 at a non-%8 size in, flow at
        input resolution out; single and batched; one compile per shape."""
        from raft_tpu import FlowEstimator

        cfg = RAFT_SMALL.replace(
            feature_encoder_widths=(8, 8, 12, 16, 24),
            context_encoder_widths=(8, 8, 12, 16, 40),
            motion_corr_widths=(16,),
            motion_flow_widths=(16, 8),
            motion_out_channels=20,
            gru_hidden=24,
            flow_head_hidden=16,
            corr_levels=2,
        )
        from raft_tpu.models.corr import CorrBlock

        model = build_raft(cfg, corr_block=CorrBlock(num_levels=2, radius=3))
        est = FlowEstimator(model, init_variables(model), num_flow_updates=2)

        im = lambda b=None: rng.integers(
            0, 255, ((130, 170, 3) if b is None else (b, 130, 170, 3)),
            dtype=np.uint8,
        )
        flow = est(im(), im())
        assert flow.shape == (130, 170, 2)
        assert np.isfinite(flow).all()
        batched = est(im(2), im(2))
        assert batched.shape == (2, 130, 170, 2)
        # padded shapes hit the %8 contract internally
        assert all(s[1] % 8 == 0 and s[2] % 8 == 0 for s in est._cache_info)

        with pytest.raises(ValueError, match="shapes differ"):
            est(im(), rng.integers(0, 255, (66, 170, 3), dtype=np.uint8))
        with pytest.raises(ValueError, match="RGB"):
            est(np.zeros((130, 170)), np.zeros((130, 170)))

    def test_normalize_heuristic(self):
        """Negative floats prove pre-normalized inputs (hard error); an
        all-positive low-max float could be a legitimately near-black raw
        frame, so it warns and proceeds (ADVICE r3)."""
        from raft_tpu.inference import FlowEstimator

        normalized = np.linspace(-1, 1, 130 * 170 * 3, dtype=np.float32)
        normalized = normalized.reshape(130, 170, 3)
        with pytest.raises(ValueError, match="already normalized"):
            FlowEstimator._normalize(normalized)

        night = np.full((130, 170, 3), 1.0, dtype=np.float32)  # max px 1.0
        with pytest.warns(UserWarning, match="near-black"):
            out = FlowEstimator._normalize(night)
        # treated as raw [0, 255]: 1.0/255*2-1
        np.testing.assert_allclose(out, 1.0 / 255.0 * 2.0 - 1.0, rtol=1e-6)

    def test_rejects_nonfinite_pixels(self):
        """NaN/Inf pixels would poison the correlation volume downstream —
        rejected at the API edge, before the range heuristic (np.max is
        NaN-poisoned, so the heuristic cannot run first)."""
        from raft_tpu.inference import FlowEstimator

        img = np.full((32, 40, 3), 128.0, dtype=np.float32)
        for bad in (np.nan, np.inf, -np.inf):
            poisoned = img.copy()
            poisoned[5, 7, 1] = bad
            with pytest.raises(ValueError, match="nonfinite"):
                FlowEstimator._normalize(poisoned)
        # uint8 input cannot be nonfinite: no scan, no false reject
        FlowEstimator._normalize(img.astype(np.uint8))


class TestInputPadderDownstream:
    """'downstream' mode (bottom-only vertical pad): only the sintel split
    path was exercised before — cover the pad/unpad round trip on odd H/W
    and batched arrays (the serve layer's bucket padding builds on it)."""

    def test_roundtrip_odd_hw(self, rng):
        img = rng.random((45, 61, 3)).astype(np.float32)
        p = InputPadder(img.shape, mode="downstream")
        assert p.pads == ((0, 3), (1, 2))  # all vertical pad at the bottom
        padded = p.pad(img)
        assert padded.shape == (48, 64, 3)
        assert padded.shape[0] % 8 == 0 and padded.shape[1] % 8 == 0
        # the valid region keeps its vertical origin (top pad is zero) and
        # the horizontal pad splits left/right
        np.testing.assert_array_equal(padded[:45, 1:62], img)
        np.testing.assert_array_equal(p.unpad(padded), img)

    def test_roundtrip_batched(self, rng):
        imgs = rng.random((2, 45, 61, 3)).astype(np.float32)
        p = InputPadder(imgs.shape, mode="downstream")
        p1, p2 = p.pad(imgs, imgs[:, ::-1])
        assert p1.shape == p2.shape == (2, 48, 64, 3)
        np.testing.assert_array_equal(p.unpad(p1), imgs)
        # flow-shaped (..., 2) arrays unpad identically to images
        flow = rng.random((2, 48, 64, 2)).astype(np.float32)
        assert p.unpad(flow).shape == (2, 45, 61, 2)

    def test_differs_from_sintel_split_only_vertically(self):
        down = InputPadder((45, 61, 3), mode="downstream")
        sintel = InputPadder((45, 61, 3), mode="sintel")
        assert sintel.pads == ((1, 2), (1, 2))  # vertical pad split top/bottom
        assert down.pads[1] == sintel.pads[1]   # horizontal identical
        # already-aligned input: both modes are a no-op
        assert InputPadder((48, 64, 3), mode="downstream").pads == ((0, 0), (0, 0))


def _load_script(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        f"script_{name}", os.path.join("scripts", f"{name}.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestValidateCLI:
    """scripts/validate.py on synthetic-layout fixtures (VERDICT r2 #10:
    the C->T stages need acceptance checks matching their training data)."""

    def test_kitti(self, tmp_path, rng, monkeypatch, capsys):
        root = tmp_path / "kitti"
        os.makedirs(root / "training/image_2")
        os.makedirs(root / "training/flow_occ")
        for i in range(2):
            img = rng.integers(0, 255, (128, 160, 3), dtype=np.uint8)
            _write_png(root / "training/image_2" / f"{i:06d}_10.png", img)
            _write_png(root / "training/image_2" / f"{i:06d}_11.png", img)
            valid = rng.uniform(0, 1, (128, 160)) > 0.3  # sparse GT
            write_flow_png(
                str(root / "training/flow_occ" / f"{i:06d}_10.png"),
                rng.uniform(-5, 5, (128, 160, 2)).astype(np.float32),
                valid,
            )
        mod = _load_script("validate")
        monkeypatch.setattr(
            "sys.argv",
            ["validate.py", str(root), "--dataset", "kitti", "--arch",
             "raft_small", "--random-init", "--iters", "2",
             "--fps-pairs", "0"],
        )
        mod.main()
        out = capsys.readouterr().out
        assert "kitti: 2 pairs" in out
        assert "f1=" in out and "epe=" in out
        # masked-EPE path: metrics finite despite sparse validity
        import re as _re

        epe = float(_re.search(r"epe=([0-9.]+)", out).group(1))
        f1 = float(_re.search(r"f1=([0-9.]+)", out).group(1))
        assert np.isfinite(epe) and 0.0 <= f1 <= 1.0

    def test_things(self, tmp_path, rng, monkeypatch, capsys):
        from raft_tpu.data.io import write_pfm

        root = tmp_path / "things"
        idir = root / "frames_cleanpass/TEST/A/0000/left"
        fdir = root / "optical_flow/TEST/A/0000/into_future/left"
        os.makedirs(idir)
        os.makedirs(fdir)
        for i in range(3):
            img = rng.integers(0, 255, (128, 160, 3), dtype=np.uint8)
            _write_png(idir / f"{i:04d}.png", img)
            write_pfm(
                str(fdir / f"OpticalFlowIntoFuture_{i:04d}_L.pfm"),
                rng.uniform(-5, 5, (128, 160, 2)).astype(np.float32),
            )
        mod = _load_script("validate")
        monkeypatch.setattr(
            "sys.argv",
            ["validate.py", str(root), "--dataset", "things", "--arch",
             "raft_small", "--random-init", "--iters", "2",
             "--fps-pairs", "0"],
        )
        mod.main()
        out = capsys.readouterr().out
        assert "things: 2 pairs" in out and "epe=" in out
