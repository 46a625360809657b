// autotune.cc — bandit arm search + GP numeric tuning (see autotune.h).
#include "autotune.h"

#include <dirent.h>
#include <sys/stat.h>
#include <unistd.h>

#include <algorithm>
#include <array>
#include <cmath>
#include <cstring>
#include <numeric>

namespace hvd {
namespace {

// RBF kernel on [0,1]^2. Length scale wide enough that ~30 samples shape a
// useful posterior (reference uses a squared-exponential GP too).
constexpr double kLen = 0.25;
constexpr double kNoise = 1e-3;

double Kern(const double* a, const double* b) {
  double d0 = a[0] - b[0], d1 = a[1] - b[1];
  return exp(-(d0 * d0 + d1 * d1) / (2.0 * kLen * kLen));
}

double NormCdf(double z) { return 0.5 * erfc(-z / sqrt(2.0)); }
double NormPdf(double z) { return exp(-0.5 * z * z) / sqrt(2.0 * M_PI); }

// Warmup grid: corners + center + edge midpoints of the log-space square,
// visited before the GP takes over. warmup[0] is also the pinned numeric
// point every categorical window (probe + halving) is measured at, so arm
// scores stay comparable.
const double kWarmup[][2] = {
    {0.5, 0.5}, {0.15, 0.15}, {0.85, 0.15}, {0.15, 0.85},
    {0.85, 0.85}, {0.5, 0.15}, {0.5, 0.85},
};
constexpr int kNumWarmup = sizeof(kWarmup) / sizeof(kWarmup[0]);

// Numeric-tail budget reserved past the categorical phases when the total
// is derived from the arm count (warmup grid + a few EI proposals).
constexpr int kNumericTail = 12;

// Largest power of two <= v (0 when v < 2).
int Pow2Floor(int v) {
  int p = 0;
  for (int b = 2; b <= v; b <<= 1) p = b;
  return p;
}

uint64_t Fnv1a(const void* p, size_t n,
               uint64_t h = 1469598103934665603ull) {
  const uint8_t* b = (const uint8_t*)p;
  for (size_t i = 0; i < n; i++) {
    h ^= b[i];
    h *= 1099511628211ull;
  }
  return h;
}

// Parsed profile file (see WriteProfile for the format).
struct TuningProfile {
  int64_t world = 0, local_size = 0;
  int wire_tier = 0;
  uint32_t dims_mask = 0;
  uint64_t tensors = 0;
  uint32_t arm_vals = 0;  // absolute categorical values, bit = AutotuneDim
  int64_t fusion = 0;
  double cycle_ms = 0.0;
  double score = 0.0;
};

// 0 ok, -1 missing/unreadable, -2 torn or corrupt (bad CRC / parse / header).
int LoadProfile(const std::string& path, TuningProfile* p) {
  FILE* f = fopen(path.c_str(), "r");
  if (!f) return -1;
  char buf[2048];
  size_t n = fread(buf, 1, sizeof(buf) - 1, f);
  fclose(f);
  buf[n] = 0;
  // The CRC line covers every byte before it; a torn write (crash between
  // fwrite and rename never happens — the writer is atomic — but a partial
  // copy or hand edit does) fails here.
  const char* crc_line = strstr(buf, "\ncrc ");
  if (!crc_line) return -2;
  size_t body_len = (size_t)(crc_line - buf) + 1;  // include the '\n'
  unsigned long long want = 0;
  if (sscanf(crc_line + 1, "crc %llx", &want) != 1) return -2;
  if (Fnv1a(buf, body_len) != (uint64_t)want) return -2;
  if (strncmp(buf, "hvd-autotune-profile v2\n", 24) != 0) return -2;
  long long world = 0, local = 0, fusion = 0;
  int wire = 0;
  unsigned dims = 0, arm_vals = 0;
  unsigned long long tensors = 0;
  double cycle = 0.0, score = 0.0;
  if (sscanf(buf + 24,
             "world %lld\nlocal %lld\nwire %d\ndims %x\ntensors %llx\n"
             "arm_vals %x\nfusion %lld\ncycle_ms %lf\nscore_mbps %lf",
             &world, &local, &wire, &dims, &tensors, &arm_vals, &fusion,
             &cycle, &score) != 9)
    return -2;
  if (fusion <= 0 || cycle <= 0.0) return -2;
  p->world = world;
  p->local_size = local;
  p->wire_tier = wire;
  p->dims_mask = dims;
  p->tensors = tensors;
  p->arm_vals = arm_vals;
  p->fusion = fusion;
  p->cycle_ms = cycle;
  p->score = score;
  return 0;
}

}  // namespace

void ParameterManager::Configure(const AutotuneConfig& cfg) {
  enabled_ = cfg.enabled;
  affinity_ = cfg.affinity.empty() ? "?" : cfg.affinity;
  if (!enabled_) return;
  cycles_per_sample_ = cfg.cycles_per_sample;
  window_cycles_ = cycles_per_sample_;
  best_fusion_ = cfg.init_fusion;
  best_cycle_ms_ = cfg.init_cycle_ms;
  bracket_cfg_ = cfg.bracket;
  profile_dir_ = cfg.profile_dir;
  world_ = cfg.world;
  local_size_ = cfg.local_size;
  wire_tier_ = cfg.wire_tier;
  profile_status_ = profile_dir_.empty() ? kProfileOff : kProfileFresh;

  // The lattice: only dims that can actually take effect become bits (a
  // capacity-0 cache, a non-uniform topology, HVD_ZEROCOPY=0, a
  // single-member ring, or a wire probe that landed on basic makes that
  // toggle a no-op; sweeping it would burn windows measuring a config that
  // never engaged). Bit order == CSV column order.
  const bool init_vals[kNumAutotuneDims] = {
      cfg.init_cache,  cfg.init_hier,   cfg.init_zerocopy,
      cfg.init_pipeline, cfg.init_shm,  cfg.init_bucket,
      cfg.init_compress, cfg.init_wire, cfg.init_alltoall};
  const bool togg[kNumAutotuneDims] = {
      cfg.can_toggle_cache,  cfg.can_toggle_hier,
      cfg.can_toggle_zerocopy, cfg.can_toggle_pipeline,
      cfg.can_toggle_shm,    cfg.can_toggle_bucket,
      cfg.can_toggle_compress, cfg.can_toggle_wire,
      cfg.can_toggle_alltoall};
  dim_count_ = 0;
  dims_mask_ = 0;
  for (int d = 0; d < kNumAutotuneDims; d++) {
    init_val_[d] = init_vals[d];
    toggleable_[d] = togg[d];
    if (togg[d]) {
      dim_id_[dim_count_++] = d;
      dims_mask_ |= 1u << d;
    }
  }
  arm_count_ = 1 << dim_count_;  // <= kMaxArms (2^9)
  cur_arm_ = 0;

  // Budget + bracket. With HVD_AUTOTUNE_MAX_SAMPLES unset/0 the budget
  // derives from the arm count: (d+1) probes + (2B-2) halving windows +
  // a numeric tail — sublinear in the 2^d lattice. An explicit budget
  // instead sizes the bracket to whatever fits after probes + a minimal
  // numeric phase.
  int d = dim_count_;
  if (cfg.max_samples <= 0) {
    int want = bracket_cfg_ > 0 ? bracket_cfg_ : 16;
    bracket0_ = Pow2Floor(std::min(want, arm_count_));
    max_samples_ =
        (d + 1) + (bracket0_ >= 2 ? 2 * bracket0_ - 2 : 0) + kNumericTail;
  } else {
    max_samples_ = cfg.max_samples;
    bracket0_ = 0;
    for (int b = 2; b <= arm_count_; b <<= 1) {
      if (bracket_cfg_ > 0 && b > bracket_cfg_) break;
      if ((d + 1) + (2 * b - 2) + 3 <= max_samples_) bracket0_ = b;
    }
  }
  // With nothing to sweep (or a budget too small for even the probes plus
  // a minimal numeric phase) skip the categorical phases and tune numerics
  // only under the initial config.
  phase_ = (d < 1 || max_samples_ < d + 4) ? kNumeric : kProbe;
  probe_idx_ = 0;

  if (!cfg.log_path.empty()) {
    log_ = fopen(cfg.log_path.c_str(), "w");
    if (log_)
      // One schema, three consumers: this header, the autotune_worker
      // assertions, and the hvdlint arm-stats rule all resolve to
      // horovod_tpu/observability/autotune_csv.py. Keep them identical.
      fprintf(log_,
              "sample,fusion_kb,cycle_ms,cache,hier,zerocopy,pipeline,shm,"
              "bucket,compress,wire,alltoall,affinity,schedule,bracket,"
              "profile,score_mbps\n");
  }
  // First sample point = warmup[0]; adopted on the first Record proposal.
  memcpy(cur_x_, kWarmup[0], sizeof(cur_x_));
}

bool ParameterManager::ArmValue(int arm_bits, int dim_id) const {
  if (!toggleable_[dim_id]) return init_val_[dim_id];
  for (int i = 0; i < dim_count_; i++)
    if (dim_id_[i] == dim_id)
      return ((arm_bits >> i) & 1) ? !init_val_[dim_id] : init_val_[dim_id];
  return init_val_[dim_id];
}

void ParameterManager::AdoptArm(int arm_bits) { cur_arm_ = arm_bits; }

double ParameterManager::ArmPrior(int arm_bits) const {
  // Multiplicative extrapolation from the single-toggle probes: each
  // flipped dim contributes its probe's speedup ratio over the baseline.
  double base = std::max(probe_score_[0], 1e-9);
  double prior = base;
  for (int i = 0; i < dim_count_; i++)
    if ((arm_bits >> i) & 1)
      prior *= std::max(probe_score_[i + 1], 1e-9) / base;
  return prior;
}

void ParameterManager::BuildBracket() {
  if (bracket0_ < 2) return;  // halving doesn't fit the budget
  std::vector<int> order(arm_count_);
  std::iota(order.begin(), order.end(), 0);
  std::stable_sort(order.begin(), order.end(), [this](int a, int b) {
    return ArmPrior(a) > ArmPrior(b);
  });
  int take = std::min(bracket0_, arm_count_);
  survivors_.assign(order.begin(), order.begin() + take);
  // A near-miss profile's arm leads the bracket: same topology, different
  // tensor digest — likely still strong here.
  if (seed_arm_ >= 0 && seed_arm_ < arm_count_) {
    survivors_.erase(
        std::remove(survivors_.begin(), survivors_.end(), seed_arm_),
        survivors_.end());
    survivors_.insert(survivors_.begin(), seed_arm_);
    survivors_.resize(take);
  }
  round_ = 0;
  round_pos_ = 0;
  round_scores_.assign(survivors_.size(), 0.0);
  window_cycles_ = cycles_per_sample_;
}

void ParameterManager::ToParams(const double x[2], int64_t* fusion,
                                double* cycle_ms) const {
  double lf = log(kFusionMinMB) +
              x[0] * (log(kFusionMaxMB) - log(kFusionMinMB));
  double lc = log(kCycleMinMs) + x[1] * (log(kCycleMaxMs) - log(kCycleMinMs));
  *fusion = (int64_t)(exp(lf) * 1024.0 * 1024.0);
  *cycle_ms = exp(lc);
}

void ParameterManager::GpFit() const {
  size_t n = xs_.size();
  // Normalize observations.
  y_mean_ = 0.0;
  for (double y : ys_) y_mean_ += y;
  y_mean_ /= (double)n;
  double var = 0.0;
  for (double y : ys_) var += (y - y_mean_) * (y - y_mean_);
  y_std_ = sqrt(var / (double)n);
  if (y_std_ < 1e-12) y_std_ = 1.0;

  // K + noise*I, Cholesky, alpha = K^-1 y (standard GP regression).
  std::vector<double> K(n * n);
  for (size_t i = 0; i < n; i++)
    for (size_t j = 0; j < n; j++) {
      K[i * n + j] = Kern(xs_[i].data(), xs_[j].data());
      if (i == j) K[i * n + j] += kNoise;
    }
  chol_.assign(n * n, 0.0);
  for (size_t i = 0; i < n; i++) {
    for (size_t j = 0; j <= i; j++) {
      double s = K[i * n + j];
      for (size_t k = 0; k < j; k++) s -= chol_[i * n + k] * chol_[j * n + k];
      if (i == j)
        chol_[i * n + i] = sqrt(std::max(s, 1e-12));
      else
        chol_[i * n + j] = s / chol_[j * n + j];
    }
  }
  // Solve L L^T alpha = y_norm.
  std::vector<double> tmp(n);
  for (size_t i = 0; i < n; i++) {
    double s = (ys_[i] - y_mean_) / y_std_;
    for (size_t k = 0; k < i; k++) s -= chol_[i * n + k] * tmp[k];
    tmp[i] = s / chol_[i * n + i];
  }
  alpha_.assign(n, 0.0);
  for (size_t ii = n; ii-- > 0;) {
    double s = tmp[ii];
    for (size_t k = ii + 1; k < n; k++) s -= chol_[k * n + ii] * alpha_[k];
    alpha_[ii] = s / chol_[ii * n + ii];
  }
}

double ParameterManager::EI(const double x[2], double best_y) const {
  size_t n = xs_.size();
  std::vector<double> kstar(n);
  for (size_t i = 0; i < n; i++) kstar[i] = Kern(x, xs_[i].data());
  double mu = 0.0;
  for (size_t i = 0; i < n; i++) mu += kstar[i] * alpha_[i];
  // var = k(x,x) - v^T v with L v = k*.
  std::vector<double> v(n);
  for (size_t i = 0; i < n; i++) {
    double s = kstar[i];
    for (size_t k = 0; k < i; k++) s -= chol_[i * n + k] * v[k];
    v[i] = s / chol_[i * n + i];
  }
  double var = 1.0 + kNoise;
  for (size_t i = 0; i < n; i++) var -= v[i] * v[i];
  double sd = sqrt(std::max(var, 1e-12));
  double best_norm = (best_y - y_mean_) / y_std_;
  double z = (mu - best_norm - 0.01) / sd;
  return (mu - best_norm - 0.01) * NormCdf(z) + sd * NormPdf(z);
}

void ParameterManager::Propose(double out[2]) {
  if (warmup_idx_ < kNumWarmup) {
    memcpy(out, kWarmup[warmup_idx_], 2 * sizeof(double));
    warmup_idx_++;
    return;
  }
  GpFit();
  double best_y = *std::max_element(ys_.begin(), ys_.end());
  double best_ei = -1.0;
  for (int c = 0; c < 512; c++) {
    // xorshift64* candidates — deterministic, no libc rand state.
    rng_ ^= rng_ >> 12;
    rng_ ^= rng_ << 25;
    rng_ ^= rng_ >> 27;
    uint64_t r = rng_ * 0x2545f4914f6cdd1dull;
    double cand[2] = {(double)(r & 0xffffffff) / 4294967296.0,
                      (double)(r >> 32) / 4294967296.0};
    double ei = EI(cand, best_y);
    if (ei > best_ei) {
      best_ei = ei;
      memcpy(out, cand, 2 * sizeof(double));
    }
  }
}

// ---------------------------------------------------------------------------
// Workload signature + persisted profiles.

void ParameterManager::ObserveTensor(uint64_t h) {
  if (sig_done_ || sig_tensors_.size() >= 65536) return;
  sig_tensors_.insert(h);
}

void ParameterManager::FinalizeSignature() {
  // Order-independent digest over the deduped tensor set: std::set
  // iterates sorted, so identical workloads hash identically regardless
  // of negotiation order.
  uint64_t h = Fnv1a("hvdtune", 7);
  uint64_t count = sig_tensors_.size();
  h = Fnv1a(&count, sizeof(count), h);
  for (uint64_t t : sig_tensors_) h = Fnv1a(&t, sizeof(t), h);
  sig_digest_ = h;
  sig_done_ = true;
}

std::string ParameterManager::ProfileFileName(uint64_t digest) const {
  char buf[160];
  snprintf(buf, sizeof(buf),
           "hvdtune-w%lld-l%lld-t%d-d%02x-%016llx.profile",
           (long long)world_, (long long)local_size_, wire_tier_,
           dims_mask_, (unsigned long long)digest);
  return profile_dir_ + "/" + buf;
}

bool ParameterManager::TryAdoptOrSeedProfile() {
  if (profile_dir_.empty()) return false;  // kill switch: no fs access
  TuningProfile p;
  std::string exact = ProfileFileName(sig_digest_);
  int rc = LoadProfile(exact, &p);
  if (rc == 0 && p.world == world_ && p.local_size == local_size_ &&
      p.wire_tier == wire_tier_ && p.dims_mask == dims_mask_) {
    // Exact signature: adopt the tuned arm + numerics with 0 sweep
    // samples. Translate the profile's absolute values into arm bits
    // relative to THIS job's initial config (only toggleable dims move).
    int bits = 0;
    for (int i = 0; i < dim_count_; i++) {
      int d = dim_id_[i];
      bool want = (p.arm_vals >> d) & 1;
      if (want != init_val_[d]) bits |= 1 << i;
    }
    AdoptArm(bits);
    best_fusion_ = p.fusion;
    best_cycle_ms_ = p.cycle_ms;
    best_score_ = p.score * 1e6;
    profile_status_ = kProfileAdopted;
    adopted_profile_ = true;
    return true;
  }
  if (rc == 0 || rc == -2) {
    // A file with the exact name but a bad CRC, parse failure, or header
    // that contradicts its own name: corrupt — fresh search, counted.
    profile_status_ = kProfileCorrupt;
    return false;
  }
  // Near miss: same topology prefix (world/local/wire/dims), different
  // tensor digest. Its arm seeds the bracket priors; its numerics seed
  // the GP start point once that arm wins.
  char prefix[128];
  snprintf(prefix, sizeof(prefix), "hvdtune-w%lld-l%lld-t%d-d%02x-",
           (long long)world_, (long long)local_size_, wire_tier_,
           dims_mask_);
  DIR* dir = opendir(profile_dir_.c_str());
  if (!dir) return false;
  bool found = false;
  struct dirent* e;
  while (!found && (e = readdir(dir)) != nullptr) {
    const char* name = e->d_name;
    size_t len = strlen(name);
    if (len < 9 || strcmp(name + len - 8, ".profile") != 0) continue;
    if (strncmp(name, prefix, strlen(prefix)) != 0) continue;
    if (LoadProfile(profile_dir_ + "/" + name, &p) != 0) continue;
    if (p.world != world_ || p.local_size != local_size_ ||
        p.wire_tier != wire_tier_ || p.dims_mask != dims_mask_)
      continue;
    int bits = 0;
    for (int i = 0; i < dim_count_; i++) {
      int d = dim_id_[i];
      if (((p.arm_vals >> d) & 1) != (init_val_[d] ? 1 : 0)) bits |= 1 << i;
    }
    seed_arm_ = bits;
    seed_fusion_ = p.fusion;
    seed_cycle_ms_ = p.cycle_ms;
    profile_status_ = kProfileNear;
    prior_seeded_ = true;
    found = true;
  }
  closedir(dir);
  return false;
}

void ParameterManager::WriteProfile() const {
  if (profile_dir_.empty() || !sig_done_) return;
  uint32_t arm_vals = 0;
  for (int d = 0; d < kNumAutotuneDims; d++)
    if (ArmValue(cur_arm_, d)) arm_vals |= 1u << d;
  char body[1024];
  int n = snprintf(body, sizeof(body),
                   "hvd-autotune-profile v2\n"
                   "world %lld\nlocal %lld\nwire %d\ndims %02x\n"
                   "tensors %016llx\narm_vals %02x\nfusion %lld\n"
                   "cycle_ms %.6f\nscore_mbps %.3f\n",
                   (long long)world_, (long long)local_size_, wire_tier_,
                   dims_mask_, (unsigned long long)sig_digest_, arm_vals,
                   (long long)best_fusion_, best_cycle_ms_,
                   best_score_ / 1e6);
  if (n <= 0 || n >= (int)sizeof(body)) return;
  std::string path = ProfileFileName(sig_digest_);
  // Atomic publish: readers either see the whole CRC'd file or nothing.
  char tmp[32];
  snprintf(tmp, sizeof(tmp), ".tmp.%d", (int)getpid());
  std::string tmp_path = path + tmp;
  FILE* f = fopen(tmp_path.c_str(), "w");
  if (!f) return;
  fwrite(body, 1, (size_t)n, f);
  fprintf(f, "crc %016llx\n", (unsigned long long)Fnv1a(body, (size_t)n));
  fclose(f);
  if (rename(tmp_path.c_str(), path.c_str()) != 0) unlink(tmp_path.c_str());
}

// ---------------------------------------------------------------------------

void ParameterManager::FillOutputs(int64_t* fusion, double* cycle_ms,
                                   int* cache_on, int* hier_on,
                                   int* zerocopy_on, int* pipeline_on,
                                   int* shm_on, int* bucket_on,
                                   int* compress_on, int* wire_on,
                                   int* alltoall_on) const {
  ToParams(cur_x_, fusion, cycle_ms);
  *cache_on = ArmValue(cur_arm_, kDimCache) ? 1 : 0;
  *hier_on = ArmValue(cur_arm_, kDimHier) ? 1 : 0;
  *zerocopy_on = ArmValue(cur_arm_, kDimZerocopy) ? 1 : 0;
  *pipeline_on = ArmValue(cur_arm_, kDimPipeline) ? 1 : 0;
  *shm_on = ArmValue(cur_arm_, kDimShm) ? 1 : 0;
  *bucket_on = ArmValue(cur_arm_, kDimBucket) ? 1 : 0;
  *compress_on = ArmValue(cur_arm_, kDimCompress) ? 1 : 0;
  *wire_on = ArmValue(cur_arm_, kDimWire) ? 1 : 0;
  *alltoall_on = ArmValue(cur_arm_, kDimAlltoall) ? 1 : 0;
}

const char* ParameterManager::BracketLabel() const {
  static const char* kRounds[] = {"h0", "h1", "h2", "h3",
                                  "h4", "h5", "h6", "h7"};
  switch (phase_) {
    case kProbe:
      return "probe";
    case kHalving:
      return kRounds[round_ < 8 ? round_ : 7];
    default:
      return "gp";
  }
}

const char* ParameterManager::ProfileLabel() const {
  switch (profile_status_) {
    case kProfileFresh:
      return "fresh";
    case kProfileNear:
      return "near";
    case kProfileAdopted:
      return "adopted";
    case kProfileCorrupt:
      return "corrupt";
    default:
      return "-";
  }
}

void ParameterManager::EmitCsvRow(const char* sample_label,
                                  const char* bracket_label, int64_t fusion,
                                  double cyc, double score) {
  if (!log_) return;
  fprintf(log_, "%s,%.1f,%.3f,%d,%d,%d,%d,%d,%d,%d,%d,%d,%s,%s,%s,%s,%.3f\n",
          sample_label, fusion / 1024.0, cyc,
          ArmValue(cur_arm_, kDimCache) ? 1 : 0,
          ArmValue(cur_arm_, kDimHier) ? 1 : 0,
          ArmValue(cur_arm_, kDimZerocopy) ? 1 : 0,
          ArmValue(cur_arm_, kDimPipeline) ? 1 : 0,
          ArmValue(cur_arm_, kDimShm) ? 1 : 0,
          ArmValue(cur_arm_, kDimBucket) ? 1 : 0,
          ArmValue(cur_arm_, kDimCompress) ? 1 : 0,
          ArmValue(cur_arm_, kDimWire) ? 1 : 0,
          ArmValue(cur_arm_, kDimAlltoall) ? 1 : 0, affinity_.c_str(),
          pipe_schedule().c_str(), bracket_label, ProfileLabel(),
          score / 1e6);
  fflush(log_);
}

void ParameterManager::Stats(int64_t out[kStatsLen]) const {
  std::lock_guard<std::mutex> l(stats_mu_);
  out[0] = n_samples_;
  out[1] = max_samples_;
  out[2] = dim_count_;
  out[3] = arm_count_;
  out[4] = bracket0_;
  out[5] = round_;
  out[6] = (int64_t)survivors_.size();
  out[7] = profile_status_;
  out[8] = prior_seeded_ ? 1 : 0;
  out[9] = adopted_profile_ ? 1 : 0;
}

bool ParameterManager::Record(int64_t bytes, int64_t now_us, int64_t* fusion,
                              double* cycle_ms, int* cache_on, int* hier_on,
                              int* zerocopy_on, int* pipeline_on,
                              int* shm_on, int* bucket_on, int* compress_on,
                              int* wire_on, int* alltoall_on) {
  if (!active()) return false;
  if (bytes <= 0 && acc_cycles_ == 0) {
    // Idle before the window opens: keep re-stamping the start so a pause
    // between windows (eval, checkpoint, compile) is not charged to the
    // next parameter point as a spurious near-zero bytes/sec observation.
    if (window_start_us_ >= 0) window_start_us_ = now_us;
    return false;
  }
  if (window_start_us_ < 0) {
    window_start_us_ = now_us;
    // Adopt the first sample point (arm 0 = the job's initial categorical
    // config, numeric point = warmup[0]) right away.
    FillOutputs(fusion, cycle_ms, cache_on, hier_on, zerocopy_on,
                pipeline_on, shm_on, bucket_on, compress_on, wire_on,
                alltoall_on);
    warmup_idx_ = 1;
    return true;
  }
  // Only data-moving cycles advance the sample; the score still divides by
  // wall time, so idle gaps correctly depress a point's throughput.
  if (bytes > 0) {
    acc_bytes_ += bytes;
    acc_cycles_++;
  }
  if (acc_cycles_ < window_cycles_) return false;

  double secs = (now_us - window_start_us_) / 1e6;
  double score = secs > 0 ? (double)acc_bytes_ / secs : 0.0;
  acc_bytes_ = 0;
  acc_cycles_ = 0;
  window_start_us_ = now_us;

  // The first window doubles as the signature window: the profile ladder
  // runs at its close, BEFORE anything is counted as a sweep sample, so an
  // exact match adopts with samples() == 0.
  if (!sig_done_) {
    FinalizeSignature();
    if (TryAdoptOrSeedProfile()) {
      std::lock_guard<std::mutex> l(stats_mu_);
      done_ = true;
      *fusion = best_fusion_;
      *cycle_ms = best_cycle_ms_;
      *cache_on = ArmValue(cur_arm_, kDimCache) ? 1 : 0;
      *hier_on = ArmValue(cur_arm_, kDimHier) ? 1 : 0;
      *zerocopy_on = ArmValue(cur_arm_, kDimZerocopy) ? 1 : 0;
      *pipeline_on = ArmValue(cur_arm_, kDimPipeline) ? 1 : 0;
      *shm_on = ArmValue(cur_arm_, kDimShm) ? 1 : 0;
      *bucket_on = ArmValue(cur_arm_, kDimBucket) ? 1 : 0;
      *compress_on = ArmValue(cur_arm_, kDimCompress) ? 1 : 0;
      *wire_on = ArmValue(cur_arm_, kDimWire) ? 1 : 0;
      *alltoall_on = ArmValue(cur_arm_, kDimAlltoall) ? 1 : 0;
      EmitCsvRow("# adopted", "-", best_fusion_, best_cycle_ms_,
                 best_score_);
      EmitCsvRow("# final", "-", best_fusion_, best_cycle_ms_, best_score_);
      return true;
    }
  }

  {
    std::lock_guard<std::mutex> l(stats_mu_);
    n_samples_++;
  }
  {
    int64_t f;
    double c;
    ToParams(cur_x_, &f, &c);
    char label[24];
    snprintf(label, sizeof(label), "%lld", (long long)n_samples_);
    EmitCsvRow(label, BracketLabel(), f, c, score);
  }
  if (score > best_score_) {
    best_score_ = score;
    ToParams(cur_x_, &best_fusion_, &best_cycle_ms_);
  }
  if (phase_ != kNumeric && score > best_measured_arm_score_) {
    best_measured_arm_score_ = score;
    best_measured_arm_ = cur_arm_;
  }

  if (n_samples_ >= max_samples_) {
    // Budget exhausted wherever we are: lock the best measured arm and
    // the best observed numeric point, persist the profile, done.
    std::lock_guard<std::mutex> l(stats_mu_);
    done_ = true;
    if (phase_ != kNumeric) AdoptArm(best_measured_arm_);
    *fusion = best_fusion_;
    *cycle_ms = best_cycle_ms_;
    *cache_on = ArmValue(cur_arm_, kDimCache) ? 1 : 0;
    *hier_on = ArmValue(cur_arm_, kDimHier) ? 1 : 0;
    *zerocopy_on = ArmValue(cur_arm_, kDimZerocopy) ? 1 : 0;
    *pipeline_on = ArmValue(cur_arm_, kDimPipeline) ? 1 : 0;
    *shm_on = ArmValue(cur_arm_, kDimShm) ? 1 : 0;
    *bucket_on = ArmValue(cur_arm_, kDimBucket) ? 1 : 0;
    *compress_on = ArmValue(cur_arm_, kDimCompress) ? 1 : 0;
    *wire_on = ArmValue(cur_arm_, kDimWire) ? 1 : 0;
    *alltoall_on = ArmValue(cur_arm_, kDimAlltoall) ? 1 : 0;
    WriteProfile();
    EmitCsvRow("# final", "-", best_fusion_, best_cycle_ms_, best_score_);
    return true;
  }

  std::lock_guard<std::mutex> l(stats_mu_);
  switch (phase_) {
    case kProbe: {
      probe_score_[probe_idx_] = score;
      probe_idx_++;
      if (probe_idx_ <= dim_count_) {
        // Next single-toggle probe: dim probe_idx_-1 flipped alone.
        AdoptArm(1 << (probe_idx_ - 1));
      } else {
        BuildBracket();
        if (bracket0_ >= 2) {
          phase_ = kHalving;
          AdoptArm(survivors_[0]);
        } else {
          // No halving budget: lock the best single-toggle probe.
          phase_ = kNumeric;
          AdoptArm(best_measured_arm_);
          xs_.push_back({cur_x_[0], cur_x_[1]});
          ys_.push_back(best_measured_arm_score_);
          Propose(cur_x_);
        }
      }
      break;
    }
    case kHalving: {
      round_scores_[round_pos_] = score;
      round_pos_++;
      if (round_pos_ < (int)survivors_.size()) {
        AdoptArm(survivors_[round_pos_]);
        break;
      }
      // Round over: keep the top half, double the window.
      std::vector<int> idx(survivors_.size());
      std::iota(idx.begin(), idx.end(), 0);
      std::stable_sort(idx.begin(), idx.end(), [this](int a, int b) {
        return round_scores_[a] > round_scores_[b];
      });
      int keep = std::max(1, (int)survivors_.size() / 2);
      std::vector<int> next;
      next.reserve(keep);
      for (int k = 0; k < keep; k++) next.push_back(survivors_[idx[k]]);
      double winner_score = round_scores_[idx[0]];
      survivors_ = next;
      if ((int)survivors_.size() <= 1) {
        // Winner locked: the numeric GP search runs under it only.
        phase_ = kNumeric;
        window_cycles_ = cycles_per_sample_;
        AdoptArm(survivors_[0]);
        xs_.push_back({cur_x_[0], cur_x_[1]});
        ys_.push_back(winner_score);
        if (profile_status_ == kProfileNear && cur_arm_ == seed_arm_ &&
            seed_fusion_ > 0) {
          // The near-miss profile's numeric point starts the GP phase.
          double lf = log(std::max((double)seed_fusion_ / (1024.0 * 1024.0),
                                   kFusionMinMB));
          double lc = log(std::min(std::max(seed_cycle_ms_, kCycleMinMs),
                                   kCycleMaxMs));
          cur_x_[0] = (lf - log(kFusionMinMB)) /
                      (log(kFusionMaxMB) - log(kFusionMinMB));
          cur_x_[1] = (lc - log(kCycleMinMs)) /
                      (log(kCycleMaxMs) - log(kCycleMinMs));
          cur_x_[0] = std::min(1.0, std::max(0.0, cur_x_[0]));
          cur_x_[1] = std::min(1.0, std::max(0.0, cur_x_[1]));
        } else {
          Propose(cur_x_);
        }
      } else {
        round_++;
        window_cycles_ = cycles_per_sample_ << round_;
        round_pos_ = 0;
        round_scores_.assign(survivors_.size(), 0.0);
        AdoptArm(survivors_[0]);
      }
      break;
    }
    case kNumeric: {
      xs_.push_back({cur_x_[0], cur_x_[1]});
      ys_.push_back(score);
      Propose(cur_x_);
      break;
    }
  }
  FillOutputs(fusion, cycle_ms, cache_on, hier_on, zerocopy_on, pipeline_on,
              shm_on, bucket_on, compress_on, wire_on, alltoall_on);
  return true;
}

}  // namespace hvd

// ---------------------------------------------------------------------------
// Deterministic sim harness: drives the REAL search policy above on a
// synthetic score surface with a fake clock — no job, no pod. Used by
// tests/test_autotune_v2.py to measure
// samples-to-within-5%-of-exhaustive-best and the profile adoption A/B
// against an exhaustive 2^d enumeration that would never fit a live sweep.

namespace {

hvd::ParameterManager* g_sim = nullptr;
int64_t g_sim_now_us = 0;
int64_t g_sim_fusion = 0;
double g_sim_cycle = 0.0;
int g_sim_cat[9] = {};
int g_sim_arm_bits = 0;

void SimRecord(int64_t bytes) {
  g_sim->Record(bytes, g_sim_now_us, &g_sim_fusion, &g_sim_cycle,
                &g_sim_cat[0], &g_sim_cat[1], &g_sim_cat[2], &g_sim_cat[3],
                &g_sim_cat[4], &g_sim_cat[5], &g_sim_cat[6], &g_sim_cat[7],
                &g_sim_cat[8]);
  // Arm bits = the categorical outputs directly (sim inits are all-false,
  // dims 0..n-1 toggleable), so bit i == dim i flipped.
  g_sim_arm_bits = 0;
  for (int i = 0; i < 9; i++)
    if (g_sim_cat[i]) g_sim_arm_bits |= 1 << i;
}

}  // namespace

extern "C" {

int hvd_autotune_sim_begin(int n_dims, int64_t max_samples, int bracket,
                           const char* profile_dir, int64_t workload_id,
                           int64_t world) {
  if (n_dims < 0 || n_dims > hvd::kNumAutotuneDims) return -1;
  delete g_sim;
  g_sim = new hvd::ParameterManager();
  hvd::AutotuneConfig c;
  c.enabled = true;
  c.cycles_per_sample = 1;  // one sim step == one sample window
  c.max_samples = max_samples;
  c.bracket = bracket;
  c.profile_dir = profile_dir ? profile_dir : "";
  c.world = world;
  c.local_size = 1;
  c.wire_tier = 0;
  c.affinity = "sim";
  bool* init_flags[9] = {&c.init_cache,    &c.init_hier,
                         &c.init_zerocopy, &c.init_pipeline,
                         &c.init_shm,      &c.init_bucket,
                         &c.init_compress, &c.init_wire,
                         &c.init_alltoall};
  bool* togg_flags[9] = {&c.can_toggle_cache,    &c.can_toggle_hier,
                         &c.can_toggle_zerocopy, &c.can_toggle_pipeline,
                         &c.can_toggle_shm,      &c.can_toggle_bucket,
                         &c.can_toggle_compress, &c.can_toggle_wire,
                         &c.can_toggle_alltoall};
  for (int i = 0; i < 9; i++) {
    *init_flags[i] = false;
    *togg_flags[i] = i < n_dims;
  }
  g_sim->Configure(c);
  g_sim->ObserveTensor((uint64_t)workload_id);
  g_sim_now_us = 0;
  // Open the first window (adopts arm 0 at warmup[0]).
  SimRecord(1);
  return 0;
}

// Arm whose score the next sim_step should report, as a bitmask over the
// sim dims (bit i set == dim i flipped on).
int hvd_autotune_sim_arm(void) {
  if (!g_sim) return -1;
  return g_sim_arm_bits;
}

// Feed one window's score for the current arm. Returns 1 when the search
// locked (converged/adopted/budget), 0 while still searching, -1 unbegun.
int hvd_autotune_sim_step(double score) {
  if (!g_sim) return -1;
  if (!g_sim->active()) return 1;
  g_sim_now_us += 1000000;  // fake clock: one second per window
  int64_t bytes = (int64_t)(score * 1e6);
  SimRecord(bytes < 1 ? 1 : bytes);
  return g_sim->active() ? 0 : 1;
}

int hvd_autotune_sim_stats(int64_t* out) {
  if (!g_sim) return -1;
  g_sim->Stats(out);
  return 0;
}

// Locked result: arm bits + tuned numerics.
int hvd_autotune_sim_result(int* arm_bits, int64_t* fusion,
                            double* cycle_ms) {
  if (!g_sim) return -1;
  if (arm_bits) *arm_bits = g_sim_arm_bits;
  if (fusion) *fusion = g_sim->best_fusion();
  if (cycle_ms) *cycle_ms = g_sim->best_cycle_ms();
  return g_sim->active() ? 0 : 1;
}

int hvd_autotune_sim_end(void) {
  delete g_sim;
  g_sim = nullptr;
  return 0;
}

}  // extern "C"
