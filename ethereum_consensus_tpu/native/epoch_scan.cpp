// The columnar epoch pass's registry-wide scans in one sweep over the
// columns (models/epoch_vector.py _sync): the u64 lane guards' maxima, the
// three activity masks, their counts and the masked effective-balance sums
// that the product guard and the justification read.
//
// Exported (C ABI):
//   uint32_t ec_epoch_scan(size_t n,
//       const uint64_t* balances, const uint64_t* eff, const uint64_t* act,
//       const uint64_t* exit, const uint64_t* wdr, const uint8_t* slashed,
//       const uint8_t* prev_part, const uint8_t* cur_part,
//       const uint64_t* inact, uint64_t prev, uint64_t cur,
//       uint32_t target_flag, uint8_t* active_prev, uint8_t* active_cur,
//       uint8_t* eligible, uint64_t* out, uint32_t n_threads)
//
// `prev_part`, `cur_part` and `inact` may be null (phase0 has none); the
// two target sums are then 0. `out` receives kFields scalars in the order
// of the enum below (native/epoch_scan.py SCAN_FIELDS names them, in the
// same order). Every sum wraps in u64, as numpy's does: the
// caller's guard (max(eff) * n < 2^64) is what makes them exact.

#include <algorithm>
#include <cstddef>
#include <cstdint>
#include <thread>
#include <vector>

namespace {

enum Field {
  kBalanceMax,
  kEffMax,
  kExitMax,     // over the exits that are not FAR_FUTURE_EPOCH; 0 if none
  kInactMax,
  kActivePrev,  // rows in each mask
  kActiveCur,
  kEligible,
  kActiveCurEff,  // eff summed over active_cur
  kPrevTargetEff,  // over active_prev & ~slashed & TIMELY_TARGET(prev_part)
  kCurTargetEff,   // over active_cur & ~slashed & TIMELY_TARGET(cur_part)
  kFields,
};

constexpr uint64_t kFarFuture = ~uint64_t(0);

struct Columns {
  const uint64_t* balances;
  const uint64_t* eff;
  const uint64_t* act;
  const uint64_t* exit;
  const uint64_t* wdr;
  const uint8_t* slashed;
  const uint8_t* prev_part;
  const uint8_t* cur_part;
  const uint64_t* inact;
  uint64_t prev;
  uint64_t cur;
  uint32_t target_flag;
  uint8_t* active_prev;
  uint8_t* active_cur;
  uint8_t* eligible;
};

// Rows [lo, hi) into `part`: branch-free, so an interleaved mask costs
// what an all-ones one does.
template <bool kTargets>
void scan_rows(const Columns& c, size_t lo, size_t hi, uint64_t* part) {
  uint64_t bal_max = 0, eff_max = 0, exit_max = 0, inact_max = 0;
  uint64_t n_prev = 0, n_cur = 0, n_elig = 0;
  uint64_t cur_eff = 0, prev_target = 0, cur_target = 0;
  const uint64_t prev = c.prev, cur = c.cur, after_prev = c.prev + 1;
  const uint32_t flag = c.target_flag;
  for (size_t i = lo; i < hi; ++i) {
    const uint64_t a = c.act[i], x = c.exit[i], e = c.eff[i];
    const uint64_t ap = uint64_t(a <= prev) & uint64_t(prev < x);
    const uint64_t ac = uint64_t(a <= cur) & uint64_t(cur < x);
    const uint64_t sl = uint64_t(c.slashed[i] != 0);
    const uint64_t el = ap | (sl & uint64_t(after_prev < c.wdr[i]));
    c.active_prev[i] = uint8_t(ap);
    c.active_cur[i] = uint8_t(ac);
    c.eligible[i] = uint8_t(el);
    bal_max = std::max(bal_max, c.balances[i]);
    eff_max = std::max(eff_max, e);
    exit_max = std::max(exit_max, x != kFarFuture ? x : uint64_t(0));
    n_prev += ap;
    n_cur += ac;
    n_elig += el;
    cur_eff += e & (uint64_t(0) - ac);
    if (kTargets) {
      inact_max = std::max(inact_max, c.inact[i]);
      const uint64_t unslashed = sl ^ 1;
      const uint64_t tp = ap & unslashed & uint64_t((c.prev_part[i] >> flag) & 1);
      const uint64_t tc = ac & unslashed & uint64_t((c.cur_part[i] >> flag) & 1);
      prev_target += e & (uint64_t(0) - tp);
      cur_target += e & (uint64_t(0) - tc);
    }
  }
  part[kBalanceMax] = bal_max;
  part[kEffMax] = eff_max;
  part[kExitMax] = exit_max;
  part[kInactMax] = inact_max;
  part[kActivePrev] = n_prev;
  part[kActiveCur] = n_cur;
  part[kEligible] = n_elig;
  part[kActiveCurEff] = cur_eff;
  part[kPrevTargetEff] = prev_target;
  part[kCurTargetEff] = cur_target;
}

}  // namespace

extern "C" {

// The sweep over rows [0, n), split into `n_threads` contiguous ranges of
// whole 64-row blocks (no two threads write a cache line of a mask); the
// calling thread takes the first. Threads' maxima are combined by max and
// their counts and sums by u64 addition. Returns the threads that ran.
uint32_t ec_epoch_scan(size_t n, const uint64_t* balances, const uint64_t* eff,
                       const uint64_t* act, const uint64_t* exit,
                       const uint64_t* wdr, const uint8_t* slashed,
                       const uint8_t* prev_part, const uint8_t* cur_part,
                       const uint64_t* inact, uint64_t prev, uint64_t cur,
                       uint32_t target_flag, uint8_t* active_prev,
                       uint8_t* active_cur, uint8_t* eligible, uint64_t* out,
                       uint32_t n_threads) {
  const Columns c{balances,  eff,   act,   exit,  wdr,         slashed,
                  prev_part, cur_part, inact, prev, cur,         target_flag,
                  active_prev, active_cur, eligible};
  const bool targets = prev_part && cur_part && inact;
  const size_t blocks = (n + 63) / 64;
  if (n_threads < 1) n_threads = 1;
  if (n_threads > blocks) n_threads = uint32_t(std::max<size_t>(blocks, 1));
  const size_t per = (blocks + n_threads - 1) / n_threads * 64;
  std::vector<uint64_t> parts(size_t(n_threads) * kFields, 0);
  auto work = [&](size_t t) {
    const size_t lo = std::min(n, t * per), hi = std::min(n, lo + per);
    uint64_t* part = parts.data() + t * kFields;
    if (targets) {
      scan_rows<true>(c, lo, hi, part);
    } else {
      scan_rows<false>(c, lo, hi, part);
    }
  };
  std::vector<std::thread> pool;
  std::vector<uint8_t> spawned(n_threads, 0);
  try {
    pool.reserve(n_threads - 1);
    for (uint32_t t = 1; t < n_threads; ++t) {
      pool.emplace_back(work, t);
      spawned[t] = 1;
    }
  } catch (...) {
    // fewer threads than asked: the calling thread takes the rest below
  }
  work(0);
  for (auto& th : pool) th.join();
  for (uint32_t t = 1; t < n_threads; ++t) {
    if (!spawned[t]) work(t);
  }
  uint64_t total[kFields] = {0};
  for (uint32_t t = 0; t < n_threads; ++t) {
    const uint64_t* part = parts.data() + size_t(t) * kFields;
    for (int f = kBalanceMax; f <= kInactMax; ++f) {
      total[f] = std::max(total[f], part[f]);
    }
    for (int f = kActivePrev; f < kFields; ++f) total[f] += part[f];
  }
  std::copy(total, total + kFields, out);
  return uint32_t(pool.size() + 1);
}

}  // extern "C"
