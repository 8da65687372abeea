"""The plain reference of the frames -> trigger path, and the comparison
that decides ``correct``.

The reference imports nothing of the program. It takes the deployment's
trained float trees (the model's weights: feature, float threshold, float
leaf value, learning rate, prior) and its fixed-point numbers from the
configuration, and computes, in float64 numpy:

    charge frames -> y-profile (sum over time and x) -> zero suppression
    -> ap_fixed quantization -> tree traversal on the integer grid
    -> integer score -> trigger cut (its own calibration) -> keep

An event is judged only where its answer does not hang on rounding: where
every comparison on its path lies more than ``AMBIGUOUS_ELECTRONS`` from the
charge at which it flips. The program sums 168 float32 charges per profile
bin; its measured error on the chip is about 0.01 electrons (PR 11's
featurizer check), and a bin's flip point is a single value, so an event
that close to it may go either way in any float32 implementation. Every
judged answer must then equal the reference exactly.

``profile_bf16`` is the control: the same sum with the charges rounded to
bfloat16, the precision below the float32 the configuration states.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

AMBIGUOUS_ELECTRONS = 0.1
N_PROFILE = 13          # y-profile bins; feature 13 is y0
LEAF = -1


def profile_f64(frames: np.ndarray, block: int = 2048) -> np.ndarray:
    """(n, T, Y, X) charges -> (n, Y) float64 y-profile in electrons."""
    out = np.empty((len(frames), frames.shape[2]), np.float64)
    for i in range(0, len(frames), block):
        out[i:i + block] = frames[i:i + block].astype(np.float64).sum(
            axis=(1, 3))
    return out


def profile_bf16(frames: np.ndarray, block: int = 8192) -> np.ndarray:
    """The control: the y-profile of the charges rounded to bfloat16 (on
    the host, where no compiler can fold the rounding away), summed in
    float32 on the default JAX device."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sum(x.astype(jnp.float32), axis=(1, 3)))
    return np.concatenate([
        np.asarray(f(frames[i:i + block].astype(jnp.bfloat16))).astype(
            np.float64) for i in range(0, len(frames), block)])


def features(profile: np.ndarray, y0: np.ndarray,
             threshold_electrons: float) -> np.ndarray:
    """(n, 13) electrons + (n,) um -> (n, 14): zero-suppressed profile in
    ke- and y0."""
    prof = np.maximum(profile, 0.0)
    prof = np.where(prof > threshold_electrons, prof, 0.0) / 1000.0
    return np.concatenate([prof, np.asarray(y0, np.float64)[:, None]], 1)


def quantize(x, fixed: Dict) -> np.ndarray:
    """ap_fixed<width, int_bits> raw integers (value = raw / 2**frac)."""
    w, i = fixed["width"], fixed["int_bits"]
    scaled = np.asarray(x, np.float64) * 2.0 ** (w - i)
    if fixed["rounding"] == "rnd":
        scaled = scaled + 0.5
    raw = np.floor(scaled).astype(np.int64)
    lo, hi = -(1 << (w - 1)), (1 << (w - 1)) - 1
    if fixed["overflow"] == "sat":
        return np.clip(raw, lo, hi)
    return (raw - lo) % (1 << w) + lo


class Model:
    """One module's BDT on the integer grid, built from its float trees."""

    def __init__(self, trees: Sequence, learning_rate: float, f0: float,
                 fixed: Dict, threshold_electrons: float):
        if fixed["rounding"] != "trn":
            raise ValueError("the reference's ambiguity margins assume "
                             "truncating quantization")
        self.fixed = fixed
        self.scale = 2.0 ** (fixed["width"] - fixed["int_bits"])
        self.threshold_electrons = threshold_electrons
        self.trees = []
        for t in trees:
            leaf = quantize(quantize(t.value, fixed) / self.scale
                            * learning_rate, fixed)
            self.trees.append(dict(
                feature=np.asarray(t.feature), left=np.asarray(t.children_left),
                right=np.asarray(t.children_right),
                thr=quantize(t.threshold, fixed), leaf=leaf))
        self.f0 = int(quantize(np.asarray(f0), fixed))
        self.cut = None

    def _flip_margin(self, prof: np.ndarray, thr: np.ndarray) -> np.ndarray:
        """Electrons between each profile bin's charge and the charge at
        which ``quantize(x) <= thr`` flips."""
        b = 1000.0 * (thr + 1) / self.scale       # x < b, in electrons
        te = self.threshold_electrons
        m = np.where(b > te, np.abs(prof - b),
                     np.where(b > 0, np.abs(prof - te), np.inf))
        if self.fixed["overflow"] == "wrap":
            top = 1000.0 * 2.0 ** (self.fixed["int_bits"] - 1)
            m = np.minimum(m, np.abs(prof - top))
        return m

    def score(self, feats: np.ndarray, profile: np.ndarray = None):
        """(n, 14) float features -> (n,) int64 scores and, given the
        profile in electrons, each event's smallest flip margin."""
        xq = quantize(feats, self.fixed)
        n = len(xq)
        rows = np.arange(n)
        acc = np.full(n, self.f0, np.int64)
        margin = np.full(n, np.inf)
        for t in self.trees:
            node = np.zeros(n, np.int64)
            while True:
                f = t["feature"][node]
                inner = f != LEAF
                if not inner.any():
                    break
                fi = np.maximum(f, 0)
                thr = t["thr"][node]
                if profile is not None:
                    on_prof = inner & (fi < N_PROFILE)
                    p = profile[rows, np.minimum(fi, N_PROFILE - 1)]
                    margin = np.where(on_prof, np.minimum(
                        margin, self._flip_margin(p, thr)), margin)
                left = xq[rows, fi] <= thr
                node = np.where(inner, np.where(left, t["left"][node],
                                                t["right"][node]), node)
            acc += t["leaf"][node]
        return acc, margin

    def calibrate(self, feats: np.ndarray, is_pileup: np.ndarray,
                  target_signal_efficiency: float) -> int:
        """The cut whose signal efficiency on ``feats`` lies closest to the
        target, ties to the higher background rejection, then the lower
        cut: keep iff score <= cut."""
        score, _ = self.score(np.asarray(feats, np.float64))
        pu = np.asarray(is_pileup).astype(bool)
        best = None
        for c in np.unique(score):
            keep = score <= c
            se = float(keep[~pu].mean()) if (~pu).any() else float("nan")
            br = float((~keep)[pu].mean()) if pu.any() else float("nan")
            key = (abs(se - target_signal_efficiency), -br)
            if best is None or key < best[0]:
                best = (key, int(c))
        self.cut = best[1]
        return self.cut


def build_models(cfg: Dict, modules) -> List[Model]:
    """One reference model per module, each calibrated on its own training
    features (the deployment's trained float trees are the only input it
    shares with the program)."""
    out = []
    for mod in modules:
        m = Model(mod.clf.trees, mod.clf.learning_rate, mod.clf.f0,
                  cfg["classifier"]["fixed"],
                  cfg["sensor"]["threshold_electrons"])
        m.calibrate(mod.train_features, mod.train_labels,
                    cfg["training"]["target_signal_efficiency"])
        out.append(m)
    return out


def expected(models: List[Model], frames: np.ndarray, y0: np.ndarray,
             profile=profile_f64) -> Dict[str, np.ndarray]:
    """The reference answer for every pool event: (C, P) score, keep and
    flip margin in electrons."""
    C, P = y0.shape
    score = np.empty((C, P), np.int64)
    margin = np.empty((C, P))
    for c, model in enumerate(models):
        prof = profile(frames[c])
        feats = features(prof, y0[c], model.threshold_electrons)
        score[c], margin[c] = model.score(feats, prof)
    cut = np.asarray([m.cut for m in models], np.int64)[:, None]
    return {"score": score, "keep": score <= cut, "margin": margin}


def compare(want: Dict[str, np.ndarray], sub_module: np.ndarray,
            sub_pool: np.ndarray, got_seq: np.ndarray, got_chip: np.ndarray,
            got_score: np.ndarray, got_keep: np.ndarray,
            eps: float = AMBIGUOUS_ELECTRONS) -> Dict[str, int]:
    """Check every drained answer against the reference.

    ``sub_module[s]``/``sub_pool[s]`` say which module and pool event
    submission ``s`` carried (seq = index). Counts: ``missing`` submissions
    never answered, ``extra`` answers to no submission or answered twice,
    ``wrong`` judged answers whose module, score or keep differ,
    ``unjudged`` answers within ``eps`` electrons of a flip."""
    n_sub = len(sub_module)
    got_seq = np.asarray(got_seq, np.int64)
    known = (got_seq >= 0) & (got_seq < n_sub)
    counts = np.bincount(got_seq[known], minlength=n_sub)
    extra = int((~known).sum() + np.maximum(counts - 1, 0).sum())
    missing = int((counts == 0).sum())
    s = got_seq[known]
    m, p = sub_module[s], sub_pool[s]
    judged = want["margin"][m, p] > eps
    bad = ((np.asarray(got_chip)[known] != m)
           | (np.asarray(got_score)[known] != want["score"][m, p])
           | (np.asarray(got_keep)[known] != want["keep"][m, p]))
    return {"compared": int(known.sum()), "judged": int(judged.sum()),
            "unjudged": int((~judged).sum()), "wrong": int((bad & judged).sum()),
            "missing": missing, "extra": extra}


def control_wrong(want: Dict[str, np.ndarray], other: Dict[str, np.ndarray],
                  eps: float = AMBIGUOUS_ELECTRONS) -> int:
    """Judged pool events whose score or keep differ between two reference
    answers (the control's reading, with ``other`` in the program's
    place)."""
    judged = want["margin"] > eps
    bad = (other["score"] != want["score"]) | (other["keep"] != want["keep"])
    return int((bad & judged).sum())


# Each compared number, with its limit: a run is correct iff every number
# is at most its limit. The readings they were set from are in PERF.md.
LIMITS = {"wrong": 0, "missing": 0, "extra": 0}


def verdict(counts: Dict[str, int]) -> bool:
    return all(counts[k] <= v for k, v in LIMITS.items())
