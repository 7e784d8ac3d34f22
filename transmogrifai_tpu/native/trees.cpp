// Native host tree builder — the CPU twin of ops/trees.py.
//
// The XLA tree kernels are designed for the TPU regime (N >> 2^depth):
// dense per-level histograms over all 2^d nodes lower to MXU contractions
// and tile perfectly. On the host at small N with deep trees (the
// reference's default RF grid reaches maxDepth=12 -> 4096-node levels for
// 900-row Titanic) that density is pure waste: most nodes are empty or
// stopped. This builder is the occupancy-aware equivalent — per-node row
// partitions, work only on live nodes, early subtree termination — i.e.
// the same role libxgboost's C++ hist algorithm plays for the reference
// (XGBoost4J JNI, SURVEY 2.9). Semantics mirror ops/trees.py grow_tree:
//   - binned matrix with dedicated missing bin 0, present bins [1, B-1]
//   - gain = sum_k GL_k^2/(HL+l) + GR_k^2/(HR+l) - Gt_k^2/(Ht+l) with
//     sparsity-aware missing direction (left prefix keeps / drops the
//     missing-bin mass), validity = min_child_weight / min_instances /
//     min_info_gain (optionally normalized by max(Ht,1)) / gamma
//   - candidate order (feature, bin, direction) with first-max wins,
//     matching jnp.argmax over the same flattening
//   - dead node encoding feat=0, thresh=B-1, miss=0 (all rows left); a
//     dead node's subtree is provably dead (children inherit the exact
//     row set), so its mass lands at the leftmost descendant leaf.
//     One RF nuance: with per-node feature subsets the XLA path redraws
//     a new subset for the (same-rows) child at the next level and may
//     find a split there; this builder finalizes the node immediately —
//     Spark's semantics (a no-split node is a leaf). Both are defensible;
//     RF parity is statistical anyway (different bootstrap RNG).
//   - leaf = lr * -G/(H+lambda+eps) (newton) or G/(H+eps) (mean),
//     zeroed when the (H>0) row count is < 0.5
// Differences: accumulation in double (XLA: f32 tree-reduce) and its own
// splitmix64 RNG for bootstrap/feature subsets — near-tie splits and
// sampled ensembles agree statistically, not bit-for-bit.
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <cmath>
#include <vector>
#include <algorithm>

namespace {

constexpr double EPS = 1e-12;

int64_t g_group_sweeps = 0;  // histogram sweeps (tests probe grouping)

struct Rng {  // splitmix64
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed) {}
  uint64_t next() {
    uint64_t z = (s += 0x9e3779b97f4a7c15ULL);
    z = (z ^ (z >> 30)) * 0xbf58476d1ce4e5b9ULL;
    z = (z ^ (z >> 27)) * 0x94d049bb133111ebULL;
    return z ^ (z >> 31);
  }
  double uniform() { return (next() >> 11) * 0x1.0p-53; }
  int poisson(double mean) {  // Knuth; mean <= ~10 here
    double L = std::exp(-mean), p = 1.0;
    int k = 0;
    do { ++k; p *= uniform(); } while (p > L);
    return k - 1;
  }
};

struct GrowParams {
  int depth, B, K;
  double reg_lambda, min_child_weight, min_instances, min_info_gain, gamma;
  bool normalize_gain;
  double lr;
  int leaf_mode;  // 0 newton, 1 mean
  double feature_frac;  // < 1 => per-node subsets (RF)
};

inline double score(const double* g, double h, int K, double lam) {
  double s = 0.0;
  for (int k = 0; k < K; ++k) s += g[k] * g[k];
  return s / (h + lam + EPS);
}

// Grow one tree. Xb [N, F] bins (int32 or uint8 — 1-byte bins matter:
// the Xb stream is the dominant memory traffic at big N); G [N, K];
// H [N]. Outputs feat/thresh/miss [2^depth - 1] (pre-filled dead), leaf
// [2^depth, K] (pre-zeroed), and per-row payload `row_out` [N, K]
// (training-time prediction for boosting; may be null).
//
// Level pass = SEQUENTIAL sweeps over the whole row array (libxgboost's
// cache strategy): one sweep accumulates every live node's interleaved
// histogram (each uint8 row of F=64 is exactly one cache line), a second
// sweep routes rows / settles dead nodes in place via `nodeid`. The
// earlier range-partition design gathered rows per node — one cache miss
// per (row, pass) at big N. Live-node histograms are compact (allocated
// for occupied nodes only, grouped under a memory budget when a deep
// level has many live nodes), so deep trees on small data stay cheap and
// big data stays bandwidth-bound, not latency-bound.
template <typename XbT>
void grow_tree(const XbT* Xb, int64_t N, int F, const float* G,
               const float* H, const GrowParams& P,
               const uint8_t* tree_fmask, Rng& rng,
               int32_t* feat, int32_t* thresh, int32_t* miss, float* leaf,
               float* row_out, int32_t* nodeid) {
  const int B = P.B, K = P.K, depth = P.depth;
  const int M = (1 << depth) - 1;
  const int L = 1 << depth;
  for (int i = 0; i < M; ++i) { feat[i] = 0; thresh[i] = B - 1; miss[i] = 0; }
  std::memset(leaf, 0, sizeof(float) * L * K);
  const int C2 = K + 2;  // interleaved cell: [g_0..g_{K-1}, h, count]
  const size_t hist_sz = (size_t)F * B * C2;
  // histogram bytes per group; TMOG_TREE_HIST_BUDGET_MB overrides (the
  // grouping path is hard to reach with real sizes — tests shrink it)
  static const size_t BUDGET = [] {
    const char* e = std::getenv("TMOG_TREE_HIST_BUDGET_MB");
    long mb = e ? std::atol(e) : 0;
    return (size_t)(mb > 0 ? mb : 768) << 20;
  }();

  // rel node id of each row at the current level; -1 = settled
  for (int64_t r = 0; r < N; ++r) nodeid[r] = 0;

  std::vector<double> cg(K), bg(K);
  std::vector<uint8_t> node_fmask(F);

  // terminal payload for node (lvl, rel) with totals (gt, ht, ct); the
  // subtree of a dead node is provably dead (children inherit the exact
  // row set), so the mass lands at the leftmost descendant leaf
  auto leaf_value = [&](const double* gt, double ht, double ct, int lvl,
                        int rel) -> const float* {
    float* out = leaf + ((size_t)rel << (depth - lvl)) * K;
    if (ct >= 0.5)
      for (int k = 0; k < K; ++k)
        out[k] = (float)(P.lr * (P.leaf_mode == 0
                                     ? -gt[k] / (ht + P.reg_lambda + EPS)
                                     : gt[k] / (ht + EPS)));
    return out;
  };

  // split search over one node's histogram: (feature, bin, direction)
  // first-max order (matches jnp.argmax over the same flattening)
  auto search = [&](const double* hist, const double* gt, double ht,
                    double ct, const uint8_t* fmask, int* out_f,
                    int* out_t, int* out_m) {
    const double parent = score(gt, ht, K, P.reg_lambda);
    const double norm = P.normalize_gain ? std::max(ht, 1.0) : 1.0;
    double best_gain = -1.0;
    int bf = -1, bt = -1, bm = 0;
    for (int f = 0; f < F; ++f) {
      if (fmask && !fmask[f]) continue;
      const double* fcell = hist + (size_t)f * B * C2;
      const double* gm = fcell;  // missing-bin (slot 0) mass
      const double hm = fcell[K], cm = fcell[K + 1];
      for (int k = 0; k < K; ++k) cg[k] = 0.0;
      double chl = 0.0, ccl = 0.0;
      for (int b = 0; b < B; ++b) {
        const double* cell = fcell + (size_t)b * C2;
        for (int k = 0; k < K; ++k) cg[k] += cell[k];
        chl += cell[K];
        ccl += cell[K + 1];
        for (int dir = 0; dir < 2; ++dir) {
          double hl = chl, cl = ccl;
          const double* gl = cg.data();
          if (dir == 1) {  // move missing mass right
            for (int k = 0; k < K; ++k) bg[k] = cg[k] - gm[k];
            gl = bg.data();
            hl -= hm;
            cl -= cm;
          }
          const double hr = ht - hl, cr = ct - cl;
          double sr = 0.0, sl = 0.0, grk;
          for (int k = 0; k < K; ++k) {
            grk = gt[k] - gl[k];
            sr += grk * grk;
          }
          for (int k = 0; k < K; ++k) sl += gl[k] * gl[k];
          const double gain = sl / (hl + P.reg_lambda + EPS)
              + sr / (hr + P.reg_lambda + EPS) - parent;
          const bool ok = hl >= P.min_child_weight
              && hr >= P.min_child_weight && cl >= P.min_instances
              && cr >= P.min_instances && gain / norm > P.min_info_gain
              && gain > 2.0 * P.gamma;
          if (ok && gain > best_gain) {
            best_gain = gain;
            bf = f; bt = b; bm = dir;
          }
        }
      }
    }
    *out_f = bf; *out_t = bt; *out_m = bm;
  };

  std::vector<int32_t> live{0};  // sorted rel ids of occupied nodes
  std::vector<double> hists, gtot, htot, ctot;
  std::vector<int32_t> slot_of, bf_s, bt_s, bm_s;
  std::vector<const float*> dead_leaf;
  std::vector<int64_t> child_cnt;

  for (int lvl = 0; lvl < depth && !live.empty(); ++lvl) {
    const int n_live = (int)live.size();
    slot_of.assign((size_t)1 << lvl, -1);
    for (int s = 0; s < n_live; ++s) slot_of[live[s]] = s;
    gtot.assign((size_t)n_live * K, 0.0);
    htot.assign(n_live, 0.0);
    ctot.assign(n_live, 0.0);
    bf_s.assign(n_live, -1);
    bt_s.assign(n_live, B - 1);
    bm_s.assign(n_live, 0);

    const int group = std::max<int>(1, (int)std::min<size_t>(
        (size_t)n_live, BUDGET / (hist_sz * sizeof(double))));
    for (int g0 = 0; g0 < n_live; g0 += group) {
      const int g1 = std::min(n_live, g0 + group);
      ++g_group_sweeps;
      hists.assign((size_t)(g1 - g0) * hist_sz, 0.0);
      for (int64_t r = 0; r < N; ++r) {  // sequential histogram sweep
        const int32_t rel = nodeid[r];
        if (rel < 0) continue;
        const int32_t s = slot_of[rel];
        if (s < g0 || s >= g1) continue;
        double* hist = hists.data() + (size_t)(s - g0) * hist_sz;
        const XbT* xr = Xb + (size_t)r * F;
        const float* gr = G + (size_t)r * K;
        const double h = H[r];
        const double c = H[r] > 0.f ? 1.0 : 0.0;
        for (int f = 0; f < F; ++f) {
          double* cell = hist + ((size_t)f * B + xr[f]) * C2;
          for (int k = 0; k < K; ++k) cell[k] += gr[k];
          cell[K] += h;
          cell[K + 1] += c;
        }
        double* gt = gtot.data() + (size_t)s * K;
        for (int k = 0; k < K; ++k) gt[k] += gr[k];
        htot[s] += h;
        ctot[s] += c;
      }
      for (int s = g0; s < g1; ++s) {
        const uint8_t* fmask = tree_fmask;
        if (P.feature_frac < 1.0) {
          // per-node feature subset (Spark featureSubsetStrategy):
          // partial Fisher-Yates drawing kf distinct features, in live
          // (sorted-rel) order so the RNG stream is deterministic
          int kf = std::min(F, std::max(1, (int)std::ceil(P.feature_frac * F - 1e-9)));
          std::fill(node_fmask.begin(), node_fmask.end(), 0);
          std::vector<int> ids(F);
          for (int f = 0; f < F; ++f) ids[f] = f;
          for (int t = 0; t < kf; ++t) {
            int j = t + (int)(rng.next() % (uint64_t)(F - t));
            std::swap(ids[t], ids[j]);
            node_fmask[ids[t]] = 1;
          }
          fmask = node_fmask.data();
        }
        search(hists.data() + (size_t)(s - g0) * hist_sz,
               gtot.data() + (size_t)s * K, htot[s], ctot[s], fmask,
               &bf_s[s], &bt_s[s], &bm_s[s]);
      }
    }

    dead_leaf.assign(n_live, nullptr);
    for (int s = 0; s < n_live; ++s) {
      const int rel = live[s];
      if (bf_s[s] < 0) {
        dead_leaf[s] = leaf_value(gtot.data() + (size_t)s * K, htot[s],
                                  ctot[s], lvl, rel);
      } else {
        const int gi = (1 << lvl) - 1 + rel;
        feat[gi] = bf_s[s];
        thresh[gi] = bt_s[s];
        miss[gi] = bm_s[s];
      }
    }

    // sequential routing sweep: settle dead rows, advance the rest
    child_cnt.assign((size_t)2 * n_live, 0);
    for (int64_t r = 0; r < N; ++r) {
      const int32_t rel = nodeid[r];
      if (rel < 0) continue;
      const int32_t s = slot_of[rel];
      if (bf_s[s] < 0) {
        if (row_out) {
          const float* out = dead_leaf[s];
          for (int k = 0; k < K; ++k)
            row_out[(size_t)r * K + k] = out[k];
        }
        nodeid[r] = -1;
        continue;
      }
      const int32_t b = (int32_t)Xb[(size_t)r * F + bf_s[s]];
      const int right = (b > bt_s[s]) || (b == 0 && bm_s[s] > 0) ? 1 : 0;
      nodeid[r] = 2 * rel + right;
      ++child_cnt[2 * s + right];
    }

    std::vector<int32_t> nxt;
    nxt.reserve((size_t)2 * n_live);
    for (int s = 0; s < n_live; ++s) {
      if (bf_s[s] < 0) continue;
      if (child_cnt[2 * s]) nxt.push_back(2 * live[s]);
      if (child_cnt[2 * s + 1]) nxt.push_back(2 * live[s] + 1);
    }
    live.swap(nxt);
  }

  // full-depth survivors: one totals sweep -> leaves (+ row_out)
  if (!live.empty()) {
    const int n_live = (int)live.size();
    slot_of.assign((size_t)1 << depth, -1);
    for (int s = 0; s < n_live; ++s) slot_of[live[s]] = s;
    gtot.assign((size_t)n_live * K, 0.0);
    htot.assign(n_live, 0.0);
    ctot.assign(n_live, 0.0);
    for (int64_t r = 0; r < N; ++r) {
      const int32_t rel = nodeid[r];
      if (rel < 0) continue;
      const int32_t s = slot_of[rel];
      const float* gr = G + (size_t)r * K;
      double* gt = gtot.data() + (size_t)s * K;
      for (int k = 0; k < K; ++k) gt[k] += gr[k];
      htot[s] += H[r];
      ctot[s] += H[r] > 0.f ? 1.0 : 0.0;
    }
    std::vector<const float*> outp(n_live);
    for (int s = 0; s < n_live; ++s)
      outp[s] = leaf_value(gtot.data() + (size_t)s * K, htot[s], ctot[s],
                           depth, live[s]);
    if (row_out) {
      for (int64_t r = 0; r < N; ++r) {
        const int32_t rel = nodeid[r];
        if (rel < 0) continue;
        const float* out = outp[slot_of[rel]];
        for (int k = 0; k < K; ++k) row_out[(size_t)r * K + k] = out[k];
      }
    }
  }
}

void tree_feature_mask(std::vector<uint8_t>& mask, int F,
                       double feature_frac, Rng& rng) {
  mask.assign(F, 1);
  if (feature_frac >= 1.0) return;
  int kf = std::max(1, (int)std::lround(feature_frac * F));
  mask.assign(F, 0);
  std::vector<int> ids(F);
  for (int f = 0; f < F; ++f) ids[f] = f;
  for (int t = 0; t < kf; ++t) {
    int j = t + (int)(rng.next() % (uint64_t)(F - t));
    std::swap(ids[t], ids[j]);
    mask[ids[t]] = 1;
  }
}


// Binary-logistic / squared-loss boosting (ops/trees.fit_gbt twin).
// feat/thresh/miss [n_rounds, 2^depth - 1]; leaf [n_rounds, 2^depth].
template <typename XbT>
int gbt_fit_impl(const XbT* Xb, int64_t N, int32_t F, int32_t B,
                 const float* y, const float* w, int32_t loss,
                 int32_t n_rounds, int32_t depth, double lr,
                 double reg_lambda, double min_child_weight,
                 double min_instances, double min_info_gain, double gamma,
                 double subsample, double feature_frac, uint64_t seed,
                 bool normalize_gain,
                 int32_t* feat, int32_t* thresh, int32_t* miss, float* leaf,
                 float* base_out) {
  if (N <= 0 || depth < 1 || depth > 20) return 1;
  Rng rng(seed * 0x9e3779b97f4a7c15ULL + 1);
  double wsum = 0.0, wy = 0.0;
  for (int64_t r = 0; r < N; ++r) { wsum += w[r]; wy += w[r] * y[r]; }
  wsum += EPS;
  double base;
  if (loss == 0) {
    double p0 = std::min(std::max(wy / wsum, 1e-6), 1.0 - 1e-6);
    base = std::log(p0 / (1.0 - p0));
  } else {
    base = wy / wsum;
  }
  *base_out = (float)base;

  const int M = (1 << depth) - 1, L = 1 << depth;
  std::vector<float> margin(N, (float)base), g(N), h(N), step(N);
  std::vector<float> gsub(N), hsub(N);
  std::vector<int32_t> nodeid(N);
  std::vector<uint8_t> fmask;
  // normalize_gain: Spark's minInfoGain, a weighted row (the GBT family);
  // false: XGBoost's, summed over the node
  GrowParams P{depth, B, 1, reg_lambda, min_child_weight, min_instances,
               min_info_gain, gamma, normalize_gain, lr, 0, 1.0};
  for (int t = 0; t < n_rounds; ++t) {
    for (int64_t r = 0; r < N; ++r) {
      if (loss == 0) {
        const double m = margin[r];
        const double p = 1.0 / (1.0 + std::exp(-m));
        g[r] = (float)(w[r] * (p - y[r]));
        h[r] = (float)std::max((double)w[r] * p * (1.0 - p), EPS);
      } else {
        g[r] = w[r] * (margin[r] - y[r]);
        h[r] = w[r];
      }
    }
    float* gp = g.data();
    float* hp = h.data();
    if (subsample < 1.0) {
      for (int64_t r = 0; r < N; ++r) {
        const float keep = rng.uniform() < subsample ? 1.f : 0.f;
        gsub[r] = g[r] * keep;
        hsub[r] = h[r] * keep;
      }
      gp = gsub.data();
      hp = hsub.data();
    }
    tree_feature_mask(fmask, F, feature_frac, rng);
    grow_tree(Xb, N, F, gp, hp, P, fmask.data(), rng,
              feat + (size_t)t * M, thresh + (size_t)t * M,
              miss + (size_t)t * M, leaf + (size_t)t * L, step.data(),
              nodeid.data());
    for (int64_t r = 0; r < N; ++r) margin[r] += step[r];
  }
  return 0;
}

// Multiclass softmax boosting (fit_gbt_softmax twin).
// Outputs stacked [n_rounds * n_classes] trees (round-major, class-minor).
template <typename XbT>
int gbt_softmax_impl(const XbT* Xb, int64_t N, int32_t F, int32_t B,
                         const float* y, const float* w, int32_t n_classes,
                         int32_t n_rounds, int32_t depth, double lr,
                         double reg_lambda, double min_child_weight,
                         double gamma, double subsample, double feature_frac,
                         uint64_t seed, int32_t* feat, int32_t* thresh,
                         int32_t* miss, float* leaf) {
  if (N <= 0 || depth < 1 || depth > 20 || n_classes < 2) return 1;
  Rng rng(seed * 0x9e3779b97f4a7c15ULL + 2);
  const int M = (1 << depth) - 1, L = 1 << depth, C = n_classes;
  std::vector<float> margin((size_t)N * C, 0.f), p((size_t)N * C);
  std::vector<float> g(N), h(N), step(N), keep(N);
  std::vector<int32_t> nodeid(N);
  std::vector<uint8_t> fmask;
  // min_instances=1, min_info_gain=0: fit_gbt_softmax grows with
  // grow_tree's defaults for those
  GrowParams P{depth, B, 1, reg_lambda, min_child_weight, 1.0, 0.0, gamma,
               false, lr, 0, 1.0};
  for (int t = 0; t < n_rounds; ++t) {
    for (int64_t r = 0; r < N; ++r) {  // softmax over classes
      const float* mr = margin.data() + (size_t)r * C;
      float mx = mr[0];
      for (int c = 1; c < C; ++c) mx = std::max(mx, mr[c]);
      double Z = 0.0;
      for (int c = 0; c < C; ++c) Z += std::exp((double)mr[c] - mx);
      for (int c = 0; c < C; ++c)
        p[(size_t)r * C + c] = (float)(std::exp((double)mr[c] - mx) / Z);
    }
    for (int64_t r = 0; r < N; ++r)
      keep[r] = (subsample >= 1.0 || rng.uniform() < subsample) ? 1.f : 0.f;
    tree_feature_mask(fmask, F, feature_frac, rng);
    for (int c = 0; c < C; ++c) {
      for (int64_t r = 0; r < N; ++r) {
        const double pc = p[(size_t)r * C + c];
        const double yc = ((int)y[r] == c) ? 1.0 : 0.0;
        g[r] = (float)(w[r] * (pc - yc)) * keep[r];
        h[r] = (float)std::max((double)w[r] * pc * (1.0 - pc), EPS)
            * keep[r];
      }
      const size_t ti = (size_t)t * C + c;
      grow_tree(Xb, N, F, g.data(), h.data(), P, fmask.data(), rng,
                feat + ti * M, thresh + ti * M, miss + ti * M, leaf + ti * L,
                step.data(), nodeid.data());
      for (int64_t r = 0; r < N; ++r) margin[(size_t)r * C + c] += step[r];
    }
  }
  return 0;
}

// Random forest / single tree (fit_forest twin): mean-mode leaves, Poisson
// bootstrap, per-node feature subsets. G [N, K] payload (class one-hots x
// weight, or y x weight); H [N] weights. leaf [n_trees, 2^depth, K].
template <typename XbT>
int rf_fit_impl(const XbT* Xb, int64_t N, int32_t F, int32_t B,
                const float* G, const float* H, int32_t K, int32_t n_trees,
                int32_t depth, double reg_lambda, double min_instances,
                double min_info_gain, double subsample, double feature_frac,
                int32_t bootstrap, uint64_t seed, int32_t* feat,
                int32_t* thresh, int32_t* miss, float* leaf) {
  if (N <= 0 || depth < 1 || depth > 20 || K < 1) return 1;
  Rng rng(seed * 0x9e3779b97f4a7c15ULL + 3);
  const int M = (1 << depth) - 1, L = 1 << depth;
  std::vector<float> Gt((size_t)N * K), Ht(N);
  std::vector<int32_t> nodeid(N);
  GrowParams P{depth, B, (int)K, reg_lambda, 0.0, min_instances,
               min_info_gain, 0.0, true, 1.0, 1, feature_frac};
  for (int t = 0; t < n_trees; ++t) {
    for (int64_t r = 0; r < N; ++r) {
      float rw;
      if (bootstrap) rw = (float)rng.poisson(subsample);
      else rw = rng.uniform() < subsample ? 1.f : 0.f;
      Ht[r] = H[r] * rw;
      for (int k = 0; k < K; ++k)
        Gt[(size_t)r * K + k] = G[(size_t)r * K + k] * rw;
    }
    grow_tree(Xb, N, F, Gt.data(), Ht.data(), P, nullptr, rng,
              feat + (size_t)t * M, thresh + (size_t)t * M,
              miss + (size_t)t * M, leaf + (size_t)t * L * K, nullptr,
              nodeid.data());
  }
  return 0;
}

// Sum of tree payloads on binned rows (predict_forest_bins twin). Rows
// outer, trees inner: each row's bins stay in cache across the whole
// ensemble; node arrays live in L1. feat/thresh/miss [T, 2^depth - 1],
// leaf [T, 2^depth, K], out [N, K] (pre-zeroed by the caller).
template <typename XbT>
void predict_bins_impl(const XbT* Xb, int64_t N, int32_t F,
                              const int32_t* feat, const int32_t* thresh,
                              const int32_t* miss, const float* leaf,
                              int32_t T, int32_t depth, int32_t K,
                              float* out) {
  const int M = (1 << depth) - 1;
  const int L = 1 << depth;
  for (int64_t r = 0; r < N; ++r) {
    const XbT* xr = Xb + (size_t)r * F;
    float* o = out + (size_t)r * K;
    for (int t = 0; t < T; ++t) {
      const int32_t* tf = feat + (size_t)t * M;
      const int32_t* tt = thresh + (size_t)t * M;
      const int32_t* tm = miss + (size_t)t * M;
      int rel = 0;
      for (int d = 0; d < depth; ++d) {
        const int gi = (1 << d) - 1 + rel;
        const int32_t b = (int32_t)xr[tf[gi]];
        const int right = (b > tt[gi]) || (b == 0 && tm[gi] > 0) ? 1 : 0;
        rel = 2 * rel + right;
      }
      const float* lf = leaf + ((size_t)t * L + rel) * K;
      for (int k = 0; k < K; ++k) o[k] += lf[k];
    }
  }
}



// Raw-value ensemble traversal (serving): x >= thresh goes right, NaN
// follows the learned miss direction. thresh_val in raw units
// (+inf = all-left dead node, -inf = all-present-right).
void predict_raw_impl(const float* X, int64_t N, int32_t F,
                      const int32_t* feat, const float* thresh_val,
                      const int32_t* miss, const float* leaf, int32_t T,
                      int32_t depth, int32_t K, float* out) {
  const int M = (1 << depth) - 1;
  const int L = 1 << depth;
  for (int64_t r = 0; r < N; ++r) {
    const float* xr = X + (size_t)r * F;
    float* o = out + (size_t)r * K;
    for (int t = 0; t < T; ++t) {
      const int32_t* tf = feat + (size_t)t * M;
      const float* tv = thresh_val + (size_t)t * M;
      const int32_t* tm = miss + (size_t)t * M;
      int rel = 0;
      for (int d = 0; d < depth; ++d) {
        const int gi = (1 << d) - 1 + rel;
        const float x = xr[tf[gi]];
        int right;
        if (std::isnan(x)) right = tm[gi] > 0 ? 1 : 0;
        else right = x >= tv[gi] ? 1 : 0;
        rel = 2 * rel + right;
      }
      const float* lf = leaf + ((size_t)t * L + rel) * K;
      for (int k = 0; k < K; ++k) o[k] += lf[k];
    }
  }
}

}  // namespace

// C ABI: `xb_itemsize` selects the bin dtype (4 = int32, 1 = uint8 —
// 1-byte bins quarter the dominant Xb memory stream at big N).
extern "C" {

int tmog_gbt_fit(const void* Xb, int64_t N, int32_t F, int32_t B,
                 int32_t xb_itemsize, const float* y, const float* w,
                 int32_t loss, int32_t n_rounds, int32_t depth, double lr,
                 double reg_lambda, double min_child_weight,
                 double min_instances, double min_info_gain, double gamma,
                 double subsample, double feature_frac, uint64_t seed,
                 int32_t normalize_gain,
                 int32_t* feat, int32_t* thresh, int32_t* miss, float* leaf,
                 float* base_out) {
  if (xb_itemsize == 1)
    return gbt_fit_impl((const uint8_t*)Xb, N, F, B, y, w, loss, n_rounds,
                        depth, lr, reg_lambda, min_child_weight,
                        min_instances, min_info_gain, gamma, subsample,
                        feature_frac, seed, normalize_gain != 0, feat,
                        thresh, miss, leaf, base_out);
  if (xb_itemsize == 4)
    return gbt_fit_impl((const int32_t*)Xb, N, F, B, y, w, loss, n_rounds,
                        depth, lr, reg_lambda, min_child_weight,
                        min_instances, min_info_gain, gamma, subsample,
                        feature_frac, seed, normalize_gain != 0, feat,
                        thresh, miss, leaf, base_out);
  return 2;
}

int tmog_gbt_softmax_fit(const void* Xb, int64_t N, int32_t F, int32_t B,
                         int32_t xb_itemsize, const float* y, const float* w,
                         int32_t n_classes, int32_t n_rounds, int32_t depth,
                         double lr, double reg_lambda,
                         double min_child_weight, double gamma,
                         double subsample, double feature_frac,
                         uint64_t seed, int32_t* feat, int32_t* thresh,
                         int32_t* miss, float* leaf) {
  if (xb_itemsize == 1)
    return gbt_softmax_impl((const uint8_t*)Xb, N, F, B, y, w, n_classes,
                            n_rounds, depth, lr, reg_lambda,
                            min_child_weight, gamma, subsample,
                            feature_frac, seed, feat, thresh, miss, leaf);
  if (xb_itemsize == 4)
    return gbt_softmax_impl((const int32_t*)Xb, N, F, B, y, w, n_classes,
                            n_rounds, depth, lr, reg_lambda,
                            min_child_weight, gamma, subsample,
                            feature_frac, seed, feat, thresh, miss, leaf);
  return 2;
}

int tmog_rf_fit(const void* Xb, int64_t N, int32_t F, int32_t B,
                int32_t xb_itemsize, const float* G, const float* H,
                int32_t K, int32_t n_trees, int32_t depth,
                double reg_lambda, double min_instances,
                double min_info_gain, double subsample, double feature_frac,
                int32_t bootstrap, uint64_t seed, int32_t* feat,
                int32_t* thresh, int32_t* miss, float* leaf) {
  if (xb_itemsize == 1)
    return rf_fit_impl((const uint8_t*)Xb, N, F, B, G, H, K, n_trees,
                       depth, reg_lambda, min_instances, min_info_gain,
                       subsample, feature_frac, bootstrap, seed, feat,
                       thresh, miss, leaf);
  if (xb_itemsize == 4)
    return rf_fit_impl((const int32_t*)Xb, N, F, B, G, H, K, n_trees,
                       depth, reg_lambda, min_instances, min_info_gain,
                       subsample, feature_frac, bootstrap, seed, feat,
                       thresh, miss, leaf);
  return 2;
}

int64_t tmog_debug_group_sweeps(void) { return g_group_sweeps; }

int tmog_predict_raw(const float* X, int64_t N, int32_t F,
                     const int32_t* feat, const float* thresh_val,
                     const int32_t* miss, const float* leaf, int32_t T,
                     int32_t depth, int32_t K, float* out) {
  predict_raw_impl(X, N, F, feat, thresh_val, miss, leaf, T, depth, K,
                   out);
  return 0;
}

int tmog_predict_bins(const void* Xb, int64_t N, int32_t F,
                      int32_t xb_itemsize, const int32_t* feat,
                      const int32_t* thresh, const int32_t* miss,
                      const float* leaf, int32_t T, int32_t depth,
                      int32_t K, float* out) {
  if (xb_itemsize == 1) {
    predict_bins_impl((const uint8_t*)Xb, N, F, feat, thresh, miss, leaf,
                      T, depth, K, out);
    return 0;
  }
  if (xb_itemsize == 4) {
    predict_bins_impl((const int32_t*)Xb, N, F, feat, thresh, miss, leaf,
                      T, depth, K, out);
    return 0;
  }
  return 2;
}

}  // extern "C"
