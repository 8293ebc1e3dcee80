// apex_tpu native host runtime.
//
// TPU-native counterpart of the reference's C++ host layer:
//  - flatten/unflatten of tensor lists (csrc/flatten_unflatten.cpp — apex_C);
//  - the host side of the data path (the reference leans on DALI/C++ loaders
//    in its imagenet example): a threaded prefetch pipeline that gathers,
//    crops, flips and normalizes uint8 image batches into fp32/bf16 host
//    buffers ready for device transfer. On TPU the input pipeline is the
//    usual MFU ceiling (SURVEY §7 risks), and Python's GIL makes a
//    pure-python loader a bottleneck — so this work happens on C++ threads.
//
// Exposed as a plain C ABI consumed via ctypes (no pybind11 in this image).
// Build: g++ -O3 -funroll-loops -std=c++17 -shared -fPIC -pthread (apex_tpu/_native).

#include <atomic>
#include <condition_variable>
#include <cstdint>
#include <cstring>
#include <deque>
#include <mutex>
#include <random>
#include <thread>
#include <vector>

extern "C" {

// ---------------------------------------------------------------------------
// flatten / unflatten (apex_C parity)
// ---------------------------------------------------------------------------

// Copy n contiguous byte-buffers into one flat buffer. Parallelized over
// source tensors with a simple thread pool; sizes in bytes.
void atp_flatten(const uint8_t** srcs, const int64_t* sizes, int64_t n,
                 uint8_t* dst, int n_threads) {
  std::vector<int64_t> offs(n + 1, 0);
  for (int64_t i = 0; i < n; ++i) offs[i + 1] = offs[i] + sizes[i];
  if (n_threads < 1) n_threads = 1;
  std::atomic<int64_t> next{0};
  auto work = [&]() {
    int64_t i;
    while ((i = next.fetch_add(1)) < n)
      std::memcpy(dst + offs[i], srcs[i], (size_t)sizes[i]);
  };
  std::vector<std::thread> ts;
  for (int t = 1; t < n_threads; ++t) ts.emplace_back(work);
  work();
  for (auto& t : ts) t.join();
}

void atp_unflatten(const uint8_t* src, const int64_t* sizes, int64_t n,
                   uint8_t** dsts, int n_threads) {
  std::vector<int64_t> offs(n + 1, 0);
  for (int64_t i = 0; i < n; ++i) offs[i + 1] = offs[i] + sizes[i];
  if (n_threads < 1) n_threads = 1;
  std::atomic<int64_t> next{0};
  auto work = [&]() {
    int64_t i;
    while ((i = next.fetch_add(1)) < n)
      std::memcpy(dsts[i], src + offs[i], (size_t)sizes[i]);
  };
  std::vector<std::thread> ts;
  for (int t = 1; t < n_threads; ++t) ts.emplace_back(work);
  work();
  for (auto& t : ts) t.join();
}

// ---------------------------------------------------------------------------
// fp32 -> bf16 (round-to-nearest-even), threaded
// ---------------------------------------------------------------------------

static inline uint16_t f32_to_bf16(float f) {
  uint32_t x;
  std::memcpy(&x, &f, 4);
  // NaN-safe RNE truncation
  if ((x & 0x7fffffffu) > 0x7f800000u) return (uint16_t)((x >> 16) | 0x0040u);
  uint32_t lsb = (x >> 16) & 1u;
  x += 0x7fffu + lsb;
  return (uint16_t)(x >> 16);
}

void atp_f32_to_bf16(const float* src, uint16_t* dst, int64_t n,
                     int n_threads) {
  if (n_threads < 1) n_threads = 1;
  int64_t chunk = (n + n_threads - 1) / n_threads;
  auto work = [&](int64_t lo, int64_t hi) {
    for (int64_t i = lo; i < hi; ++i) dst[i] = f32_to_bf16(src[i]);
  };
  std::vector<std::thread> ts;
  for (int t = 1; t < n_threads; ++t) {
    int64_t lo = t * chunk, hi = std::min(n, lo + chunk);
    if (lo < hi) ts.emplace_back(work, lo, hi);
  }
  work(0, std::min(n, chunk));
  for (auto& t : ts) t.join();
}

// ---------------------------------------------------------------------------
// Image batch transform: gather + random-crop + hflip + normalize,
// uint8 HWC -> fp32/bf16 HWC.
// ---------------------------------------------------------------------------

struct TransformSpec {
  int64_t src_h, src_w, c;     // source image dims
  int64_t out_h, out_w;        // crop dims (<= src)
  float mean[8], std_inv[8];   // per-channel (c <= 8)
  int out_bf16;                // 0 = f32, 1 = bf16
  int augment;                 // 1 = random crop + hflip, 0 = center crop
};

// One image: crop at (y0,x0), optional horizontal flip, normalize.
static void transform_one(const uint8_t* src, void* dst,
                          const TransformSpec& sp, int64_t y0, int64_t x0,
                          bool flip) {
  const int64_t C = sp.c, W = sp.src_w;
  float* f32 = (float*)dst;
  uint16_t* b16 = (uint16_t*)dst;
  for (int64_t y = 0; y < sp.out_h; ++y) {
    const uint8_t* row = src + ((y0 + y) * W + x0) * C;
    int64_t obase = y * sp.out_w * C;
    for (int64_t x = 0; x < sp.out_w; ++x) {
      int64_t sx = flip ? (sp.out_w - 1 - x) : x;
      const uint8_t* px = row + sx * C;
      int64_t o = obase + x * C;
      for (int64_t ch = 0; ch < C; ++ch) {
        float v = ((float)px[ch] * (1.0f / 255.0f) - sp.mean[ch]) *
                  sp.std_inv[ch];
        if (sp.out_bf16) b16[o + ch] = f32_to_bf16(v);
        else f32[o + ch] = v;
      }
    }
  }
}

// Synchronous batch transform (also the worker-thread body below).
// images: base of the uint8 dataset [N, src_h, src_w, c];
// indices: which images; dst: [n, out_h, out_w, c] f32 or bf16.
void atp_transform_batch(const uint8_t* images, const int64_t* indices,
                         int64_t n, const TransformSpec* sp, void* dst,
                         uint64_t seed, int n_threads) {
  if (n_threads < 1) n_threads = 1;
  const int64_t img_bytes = sp->src_h * sp->src_w * sp->c;
  const int64_t out_elems = sp->out_h * sp->out_w * sp->c;
  const int64_t out_bytes = out_elems * (sp->out_bf16 ? 2 : 4);
  std::atomic<int64_t> next{0};
  auto work = [&]() {
    int64_t i;
    while ((i = next.fetch_add(1)) < n) {
      std::mt19937_64 rng(seed * 0x9e3779b97f4a7c15ULL + (uint64_t)i);
      int64_t max_y = sp->src_h - sp->out_h, max_x = sp->src_w - sp->out_w;
      int64_t y0 = max_y / 2, x0 = max_x / 2;
      bool flip = false;
      if (sp->augment) {
        y0 = max_y ? (int64_t)(rng() % (uint64_t)(max_y + 1)) : 0;
        x0 = max_x ? (int64_t)(rng() % (uint64_t)(max_x + 1)) : 0;
        flip = (rng() & 1) != 0;
      }
      transform_one(images + indices[i] * img_bytes,
                    (uint8_t*)dst + i * out_bytes, *sp, y0, x0, flip);
    }
  };
  std::vector<std::thread> ts;
  for (int t = 1; t < n_threads; ++t) ts.emplace_back(work);
  work();
  for (auto& t : ts) t.join();
}

// Flat-argument wrapper (ctypes-friendly: no struct marshalling).
void atp_transform_batch_args(const uint8_t* images, const int64_t* indices,
                              int64_t n, int64_t src_h, int64_t src_w,
                              int64_t c, int64_t out_h, int64_t out_w,
                              const float* mean, const float* stdv,
                              int out_bf16, int augment, void* dst,
                              uint64_t seed, int n_threads) {
  TransformSpec sp;
  sp.src_h = src_h;
  sp.src_w = src_w;
  sp.c = c;
  sp.out_h = out_h;
  sp.out_w = out_w;
  for (int64_t i = 0; i < c && i < 8; ++i) {
    sp.mean[i] = mean[i];
    sp.std_inv[i] = 1.0f / stdv[i];
  }
  sp.out_bf16 = out_bf16;
  sp.augment = augment;
  atp_transform_batch(images, indices, n, &sp, dst, seed, n_threads);
}

// ---------------------------------------------------------------------------
// Prefetching loader: worker threads transform upcoming batches into a
// bounded ring of host buffers (the DALI-style double-buffer analog).
// ---------------------------------------------------------------------------

struct Job {
  std::vector<int64_t> indices;
  uint64_t seed;
  int64_t slot;
  uint64_t seq;   // submit order; next() delivers in this order
};

struct Loader {
  const uint8_t* images;   // borrowed; owner is the Python side (np array)
  TransformSpec sp;
  int64_t batch;
  int64_t out_bytes_per_batch;
  std::vector<std::vector<uint8_t>> slots;   // capacity buffers
  std::deque<Job> pending;                   // submitted, not yet started
  std::deque<std::pair<uint64_t, int64_t>> ready;  // (seq, slot), any order
  std::vector<int64_t> free_slots;
  uint64_t submit_seq = 0, deliver_seq = 0;
  std::mutex mu;
  std::condition_variable cv_worker, cv_ready, cv_free;
  std::vector<std::thread> workers;
  bool stop = false;
  int inner_threads;

  void worker() {
    for (;;) {
      Job job;
      {
        std::unique_lock<std::mutex> lk(mu);
        cv_worker.wait(lk, [&] { return stop || !pending.empty(); });
        if (stop) return;
        job = std::move(pending.front());
        pending.pop_front();
      }
      atp_transform_batch(images, job.indices.data(),
                          (int64_t)job.indices.size(), &sp,
                          slots[job.slot].data(), job.seed, inner_threads);
      {
        std::lock_guard<std::mutex> lk(mu);
        ready.emplace_back(job.seq, job.slot);
      }
      cv_ready.notify_all();
    }
  }
};

void* atp_loader_create(const uint8_t* images, int64_t src_h, int64_t src_w,
                        int64_t c, int64_t out_h, int64_t out_w,
                        const float* mean, const float* stdv, int out_bf16,
                        int augment, int64_t batch, int capacity,
                        int n_workers, int inner_threads) {
  auto* L = new Loader();
  L->images = images;
  L->sp.src_h = src_h;
  L->sp.src_w = src_w;
  L->sp.c = c;
  L->sp.out_h = out_h;
  L->sp.out_w = out_w;
  for (int64_t i = 0; i < c && i < 8; ++i) {
    L->sp.mean[i] = mean[i];
    L->sp.std_inv[i] = 1.0f / stdv[i];
  }
  L->sp.out_bf16 = out_bf16;
  L->sp.augment = augment;
  L->batch = batch;
  L->out_bytes_per_batch = batch * out_h * out_w * c * (out_bf16 ? 2 : 4);
  L->inner_threads = inner_threads < 1 ? 1 : inner_threads;
  L->slots.resize(capacity);
  for (int i = 0; i < capacity; ++i) {
    L->slots[i].resize((size_t)L->out_bytes_per_batch);
    L->free_slots.push_back(i);
  }
  for (int i = 0; i < (n_workers < 1 ? 1 : n_workers); ++i)
    L->workers.emplace_back(&Loader::worker, L);
  return L;
}

// Enqueue one batch of indices; blocks if no free slot (bounded prefetch).
void atp_loader_submit(void* handle, const int64_t* indices, int64_t n,
                       uint64_t seed) {
  auto* L = (Loader*)handle;
  Job job;
  job.indices.assign(indices, indices + n);
  job.seed = seed;
  {
    std::unique_lock<std::mutex> lk(L->mu);
    L->cv_free.wait(lk, [&] { return L->stop || !L->free_slots.empty(); });
    if (L->stop) return;
    job.slot = L->free_slots.back();
    L->free_slots.pop_back();
    job.seq = L->submit_seq++;
    L->pending.push_back(std::move(job));
  }
  L->cv_worker.notify_one();
}

// Block until the next batch *in submit order* is ready, copy it out,
// release the slot. Returns bytes copied or -1 on shutdown.
int64_t atp_loader_next(void* handle, uint8_t* dst) {
  auto* L = (Loader*)handle;
  int64_t slot = -1;
  {
    std::unique_lock<std::mutex> lk(L->mu);
    uint64_t want = L->deliver_seq;
    auto find = [&]() -> bool {
      for (auto it = L->ready.begin(); it != L->ready.end(); ++it) {
        if (it->first == want) {
          slot = it->second;
          L->ready.erase(it);
          return true;
        }
      }
      return false;
    };
    L->cv_ready.wait(lk, [&] { return L->stop || find(); });
    if (slot < 0) return -1;
    L->deliver_seq = want + 1;
  }
  std::memcpy(dst, L->slots[slot].data(), (size_t)L->out_bytes_per_batch);
  {
    std::lock_guard<std::mutex> lk(L->mu);
    L->free_slots.push_back(slot);
  }
  L->cv_free.notify_one();
  return L->out_bytes_per_batch;
}

void atp_loader_destroy(void* handle) {
  auto* L = (Loader*)handle;
  {
    std::lock_guard<std::mutex> lk(L->mu);
    L->stop = true;
  }
  L->cv_worker.notify_all();
  L->cv_ready.notify_all();
  L->cv_free.notify_all();
  for (auto& t : L->workers) t.join();
  delete L;
}

int atp_version() { return 1; }

}  // extern "C"
